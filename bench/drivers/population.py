"""Driver ``population``: cross-device federations of 10^5 clients.

Each call is one ``run_fl_population`` over ``seeds_per_call`` seed lanes
of ``rounds`` rounds (eval every ``eval_every``) on one population drawn
from the run seed: every round scores all clients, takes a cohort of at
most ``k_max`` on the device, and trains only the cohort.  Calls go back to
back as in the ``sweep`` driver, and are measured and checked the same
way (``lane_rounds_per_s``; the first eval block of the lanes of calls 0,
1 and 2 against the plain reference).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.drivers import common, sweep
from bench.gen import data as gen


class Driver(sweep.Driver):
    def prepare(self):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import FLConfig
        from repro.data.synthetic import Population
        cfg, tr = self.cfg, self.tr
        p = gen.population(self.cell.seed, cfg["dataset"], tr["n_clients"],
                           cfg["n_samples"], tr["members_per_client"],
                           alpha=cfg["alpha"], test_frac=cfg["test_frac"],
                           feature_shift=cfg["feature_shift"])
        self.pd = p
        self.pop = Population(
            pool_x=jnp.asarray(p.pool_x), pool_y=jnp.asarray(p.pool_y),
            member_idx=jnp.asarray(p.member_idx),
            member_size=jnp.asarray(p.member_size),
            data_size=jnp.asarray(p.data_size),
            data_quality=jnp.asarray(p.data_quality),
            shift_key=jax.random.key(np.uint32(p.shift_seed)),
            test_x=jnp.asarray(p.test_x), test_y=jnp.asarray(p.test_y),
            feature_shift=p.feature_shift, feature_shape=None)
        self.fl = FLConfig(model=cfg["model"], **dict(cfg["fl"], **tr["fl"]))
        self.grid = [{}]
        self.n_lanes = tr["seeds_per_call"]
        self.calls = 0

    def _call(self):
        from repro.train import fl_driver
        c = self.calls
        seeds = common.lane_seeds(self.cell.seed, c, self.tr["seeds_per_call"])
        with common.precision(self.cfg):
            res = fl_driver.run_fl_population(
                self.pop, self.fl, seeds=seeds, method="proposed",
                rounds=self.tr["rounds"], eval_every=self.tr["eval_every"],
                dataset=self.cfg["dataset"], hidden=self.cfg["hidden"])
        self.calls += 1
        lanes = [r for row in res for r in row]
        if c < sweep.N_CHECKED_CALLS:
            self.records.append({
                "seeds": seeds,
                "hist": {k: [l.history[k][0] for l in lanes]
                         for k in ("loss", "acc", "auc", "k", "fail")}})
        return lanes

    def _work_meta(self) -> Dict:
        fl = dict(self.cfg["fl"], **self.tr["fl"])
        return {"model": self.cfg["model"], "hidden": self.cfg["hidden"],
                "n_features": int(self.pd.pool_x.shape[1]),
                "feature_shape": [int(self.pd.pool_x.shape[1])],
                "n_test": int(self.pd.test_x.shape[0]),
                "local_steps": fl["local_epochs"],
                "local_batch": fl["local_batch"]}

    def free(self):
        import gc
        self.pop = None
        gc.collect()

    def reference(self, mode: str) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp
        from bench.ref import fl as ref_fl
        model = self.cell.ref_module().build(self._work_meta())
        fl = dict(self.cfg["fl"], **self.tr["fl"])
        run = ref_fl.population_block(model, fl, self.tr["eval_every"], mode)
        p = self.pd
        pop = {"pool_x": jnp.asarray(p.pool_x), "pool_y": jnp.asarray(p.pool_y),
               "member_idx": jnp.asarray(p.member_idx),
               "member_size": jnp.asarray(p.member_size),
               "data_size": jnp.asarray(p.data_size),
               "data_quality": jnp.asarray(p.data_quality),
               "test_x": jnp.asarray(p.test_x), "test_y": jnp.asarray(p.test_y),
               "shift_key": jax.random.key(np.uint32(p.shift_seed)),
               "feature_shift": p.feature_shift}
        keys = jnp.stack([
            jax.random.key(np.uint32(s))
            for c in range(len(self.records) or sweep.N_CHECKED_CALLS)
            for s in common.lane_seeds(self.cell.seed, c,
                                       self.tr["seeds_per_call"])])
        eps = jnp.full((keys.shape[0],), fl["dp_epsilon"], jnp.float32)
        out = jax.jit(lambda k, e: jax.lax.map(
            lambda a: run(a[0], a[1], pop), (k, e)))(keys, eps)
        return dict(zip(("loss", "acc", "auc", "k", "fail"),
                        (np.asarray(o) for o in out)))
