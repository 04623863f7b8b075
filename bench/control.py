#!/usr/bin/env python3
"""Readings that the correctness limits are set from, at a cell's own size.

    python bench/control.py --workload <name> --seeds 1 2 3 [--program]

For each seed it prints the numbers the cell's check compares for the
control: the plain reference computed one precision step below the
configuration's (``bench/ref/prec.py``), put in the program's place and
compared with the reference.  With ``--program`` it also prints the
program's own readings on the same seeds (its first calls only, no
measured window).  One JSON line per seed and side; the benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(workload: str, seeds, program: bool, cell_hook=None,
             require_chip: bool = True, root: str = ROOT):
    from bench import harness
    from bench.drivers import common
    from bench.ref import prec
    import importlib
    cell = harness.resolve(workload, root)
    if cell_hook is not None:
        cell_hook(cell)
    harness.setup_environment()
    import jax
    devices = (harness.chip_devices(cell.chips) if require_chip
               else jax.devices()[:1])
    mod = importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")
    out = []
    for seed in seeds:
        cell.seed = int(seed)
        drv = mod.Driver(cell, devices)
        t0 = time.time()
        drv.prepare()
        if program:
            nums = drv.program_readings()
            out.append({"seed": seed, "side": "program", **nums,
                        "s": time.time() - t0})
            print(json.dumps(out[-1]), flush=True)
        mode = common.ref_mode(cell.config)
        nums = drv.control_readings(prec.lower(mode))
        out.append({"seed": seed, "side": "control", **nums,
                    "s": time.time() - t0})
        print(json.dumps(out[-1]), flush=True)
        drv.free()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    readings(args.workload, args.seeds, args.program)
    return 0


if __name__ == "__main__":
    sys.exit(main())
