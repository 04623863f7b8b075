#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json`` at the root of the checkout.  The last line of standard
output is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, for ``--trace 1``, ``breakdown``; its last key,
``check``, and the last lines of standard error give each number the
correctness check compared, beside its limit.  With no accelerator, or
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
