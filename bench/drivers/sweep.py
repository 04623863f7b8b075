"""Driver ``sweep``: research sweeps of the compiled FL program.

Each call is one ``run_fl_sweep`` over the traffic's grid of runtime
hyper-parameters × ``seeds_per_call`` seeds (the lanes), ``rounds``
rounds with an eval every ``eval_every``.  Calls are made back to back;
call ``c`` uses lane seeds ``seed + c·seeds_per_call + j`` and the one
federation drawn from the run seed.  Set-up makes call 0 (which compiles
or loads the program); the window makes calls 1, 2, ... until ``seconds``
have passed, finishing the one running then.

End to end: ``lane_rounds_per_s``, the lanes × rounds of every call in the
window over the window's wall time.  Correctness: the first eval block of
every lane of calls 0, 1 and 2 (0 and 1 where the window held one call),
against the plain reference; the window's later calls and blocks are not
compared (``bench/drivers/common.py::gap_numbers`` says why).
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np

from bench.drivers import common
from bench.gen import data as gen
from bench.harness import Window

N_CHECKED_CALLS = 3


class Driver:
    def __init__(self, cell, devices):
        self.cell = cell
        self.cfg = cell.config
        self.tr = cell.traffic
        self.devices = devices
        self.records: List[Dict] = []       # first-block history of checked calls
        self.traced = {"lane_rounds": 0, "calls": 0, "cohort_rounds": 0.0,
                       "eval_blocks": 0}

    # -- the program ------------------------------------------------------

    def prepare(self):
        """The run's data and the program's config objects."""
        from repro.configs.base import FLConfig
        cfg, tr = self.cfg, self.tr
        self.fd = gen.federation(
            self.cell.seed, cfg["dataset"], cfg["n_samples"], cfg["fl"]["n_clients"],
            alpha=cfg["alpha"], test_frac=cfg["test_frac"],
            feature_shift=cfg["feature_shift"], size_seed=cfg["size_seed"])
        self.fed = common.program_federation(self.fd)
        self.fl = FLConfig(model=cfg["model"], **cfg["fl"])
        self.grid = [{tr["grid_key"]: v} for v in tr["grid"]]
        self.n_lanes = len(self.grid) * tr["seeds_per_call"]
        self.calls = 0

    def setup(self):
        self.prepare()
        self._call()

    def _call(self):
        from repro.train import fl_driver
        c = self.calls
        seeds = common.lane_seeds(self.cell.seed, c, self.tr["seeds_per_call"])
        with common.precision(self.cfg):
            res = fl_driver.run_fl_sweep(
                self.fed, self.fl, self.grid, seeds=seeds, method="proposed",
                rounds=self.tr["rounds"], eval_every=self.tr["eval_every"],
                dataset=self.cfg["dataset"], hidden=self.cfg["hidden"])
        self.calls += 1
        lanes = [r for row in res for r in row]
        if c < N_CHECKED_CALLS:
            self.records.append({
                "seeds": seeds,
                "hist": {k: [l.history[k][0] for l in lanes]
                         for k in ("loss", "acc", "auc", "k", "fail")}})
        return lanes

    def window(self, seconds: float, prof=None) -> Window:
        rounds, every = self.tr["rounds"], self.tr["eval_every"]
        trace_from = seconds * self.tr.get("trace_after", 0.3)
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            if prof is not None and prof.state == "idle" \
                    and time.perf_counter() - t0 >= trace_from:
                prof.begin()
            lanes = self._call()
            n += 1
            if prof is not None and prof.on:
                self._count_traced(lanes, rounds, every)
                if self.traced["calls"] >= self.tr.get("trace_calls", 1):
                    prof.end()
        wall = time.perf_counter() - t0
        return Window(attempted=n, failed=0,
                      e2e={"lane_rounds_per_s": n * self.n_lanes * rounds / wall},
                      traced=dict(self.traced, **self._work_meta()))

    def _count_traced(self, lanes, rounds, every):
        t = self.traced
        t["calls"] += 1
        t["lane_rounds"] += len(lanes) * rounds
        for lane in lanes:
            done = 0
            for k in lane.history["k"]:
                block = min(every, rounds - done)
                t["cohort_rounds"] += math.ceil(k - 1e-6) * block
                done += block
            t["eval_blocks"] += len(lane.history["k"])

    def _work_meta(self) -> Dict:
        return {"model": self.cfg["model"], "hidden": self.cfg["hidden"],
                "n_features": self.fd.n_features,
                "feature_shape": list(self.fd.feature_shape),
                "n_test": int(self.fd.test_x.shape[0]),
                "local_steps": self.cfg["fl"]["local_epochs"],
                "local_batch": self.cfg["fl"]["local_batch"]}

    def free(self):
        from repro.train import fl_driver
        self.fed = None
        fl_driver._STACK_CACHE.clear()
        import gc
        gc.collect()

    # -- the check --------------------------------------------------------

    def reference(self, mode: str) -> Dict[str, np.ndarray]:
        """The reference's first-block history of the checked calls' lanes,
        at ``mode`` (``bench/ref/prec.py``)."""
        import jax
        import jax.numpy as jnp
        from bench.ref import fl as ref_fl
        model = self.cell.ref_module().build(self._work_meta())
        run = ref_fl.dense_block(model, dict(self.cfg["fl"]),
                                 self.tr["eval_every"], mode)
        st = {k: jnp.asarray(v) for k, v in
              common.reference_stack(self.fd).items()}
        keys, vals = [], []
        for c in range(len(self.records) or N_CHECKED_CALLS):
            seeds = common.lane_seeds(self.cell.seed, c, self.tr["seeds_per_call"])
            for v in self.tr["grid"]:
                for s in seeds:
                    keys.append(jax.random.key(np.uint32(s)))
                    vals.append(v)
        keys = jnp.stack(keys)
        vals = jnp.asarray(vals, jnp.float32)
        if self.tr["grid_key"] != "dp_epsilon":
            raise ValueError("the reference sweeps dp_epsilon only")
        out = jax.jit(lambda k, e: jax.lax.map(
            lambda a: run(a[0], a[1], st), (k, e)))(keys, vals)
        return dict(zip(("loss", "acc", "auc", "k", "fail"),
                        (np.asarray(o) for o in out)))

    def program_history(self) -> Dict[str, np.ndarray]:
        return {k: np.concatenate([np.asarray(r["hist"][k], np.float64)
                                   for r in self.records])
                for k in ("loss", "acc", "auc", "k", "fail")}

    def check(self):
        if len(self.records) < 2:
            raise RuntimeError("the check needs the set-up call and one "
                               "call of the window")
        ref = self.reference(common.ref_mode(self.cfg))
        nums = common.gap_numbers(self.program_history(), ref)
        limits = common.limits(self.cell.name)
        return [(k, nums[k], limits[k]) for k in limits]

    # -- readings for the limits (bench/control.py) -----------------------

    def program_readings(self) -> Dict[str, float]:
        """The program's numbers from its first calls, no window."""
        while self.calls < N_CHECKED_CALLS:
            self._call()
        return common.gap_numbers(self.program_history(),
                                  self.reference(common.ref_mode(self.cfg)))

    def control_readings(self, mode: str) -> Dict[str, float]:
        """The reference at ``mode`` in the program's place."""
        return common.gap_numbers(self.reference(mode),
                                  self.reference(common.ref_mode(self.cfg)))
