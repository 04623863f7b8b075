#!/usr/bin/env python3
"""Record the small TPU trace that ``test_bench_trace.py`` reduces.

    python bench/tests/data/record_small_trace.py   # on one TPU chip

Inside a ``bench.segment`` host annotation: 4 calls of a jitted step that
runs a matmul under the named scope ``alpha`` and the program's
``rglru_scan`` Pallas kernel under ``beta``, then a host sleep of 50 ms
under the annotation ``gap.sleep``, then 4 more calls.  The trace lands in
``bench/tests/data/small_trace/`` and its expected counts in
``small_trace.json``.
"""
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops as kops  # noqa: E402

CALLS, SLEEP_S = 8, 0.05


@jax.jit
def step(w, a, x):
    with jax.named_scope("alpha"):
        y = jnp.tanh(w @ w)
    with jax.named_scope("beta"):
        h, _ = kops.rglru_scan(a, x)
    return y, h


def main():
    w = jnp.ones((512, 512), jnp.float32) * 1e-3
    a = jnp.full((8, 4, 512), 0.5, jnp.float32)
    x = jnp.ones((8, 4, 512), jnp.float32)
    jax.block_until_ready(step(w, a, x))
    out = os.path.join(HERE, "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.segment"):
        for i in range(CALLS):
            if i == CALLS // 2:
                with jax.profiler.TraceAnnotation("gap.sleep"):
                    time.sleep(SLEEP_S)
            jax.block_until_ready(step(w, a, x))
    jax.profiler.stop_trace()
    for f in glob.glob(os.path.join(out, "**", "*"), recursive=True):
        if os.path.isfile(f) and not f.endswith(".xplane.pb"):
            os.remove(f)
    with open(os.path.join(HERE, "small_trace.json"), "w") as f:
        json.dump({"calls": CALLS, "sleep_s": SLEEP_S,
                   "kernel": "_rglru_scan", "scopes": ["alpha", "beta"]}, f)


if __name__ == "__main__":
    main()
