"""Driver ``serve_open``: anomaly scoring of live windows, open loop.

Windows arrive on a fixed schedule drawn from the seed (``rate`` windows
per second for the window's ``seconds``; exactly ``rate·seconds`` arrivals
at sorted uniform times, each a window of the seeded ROAD test pool), and
are fed to ``ServeEngine.score_stream`` by a generator that yields, each
time it is pulled, every window that has come due, at most once every
``poll_ms`` (a receive loop polling its socket), sleeping until then when
none has.  The engine's own bucketing, feed and dispatch do the rest, as
in a deployment.

End to end: ``score_p95_ms``, the 95th percentile over every window
offered of the time from when it was due to when its batch's scores were
ready on the host (a window not scored counts as missing).  Correctness:
the scores of a seeded sample of the windows scored, against the plain
reference's scores of the same windows.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from bench.drivers import common
from bench.gen import data as gen
from bench.harness import Window


class Driver:
    def __init__(self, cell, devices):
        self.cell = cell
        self.cfg = cell.config
        self.tr = cell.traffic
        self.devices = devices
        self.scores = None

    def prepare(self):
        import jax
        from repro.models.spec import DataMeta, get_model_spec
        from repro.serve.engine import ServeEngine
        cfg, tr = self.cfg, self.tr
        rng = np.random.default_rng(self.cell.seed)
        x, _, shape = gen.samples(rng, cfg["dataset"], cfg["n_samples"])
        n_test = int(cfg["n_samples"] * cfg["test_frac"])
        self.pool = x[rng.permutation(len(x))[:n_test]]
        self.meta = DataMeta(n_features=x.shape[1], n_classes=2,
                             hidden=cfg["hidden"],
                             feature_shape=tuple(shape))
        self.spec = get_model_spec(cfg["model"], self.meta)
        key = jax.random.key(np.uint32(gen.seed32(self.cell.seed)))
        self.params = jax.jit(self.spec.init)(key)
        self.engine = ServeEngine(self.spec, self.meta, self.params,
                                  buckets=tuple(tr["buckets"]),
                                  route=tr["route"])
        self.due, self.idx = gen.open_loop_arrivals(
            self.cell.seed, tr["rate"], self.cell.seconds, len(self.pool))

    def setup(self):
        self.prepare()
        with common.precision(self.cfg):
            self.engine.warmup()
            # one pass over a full and a padded batch of real windows
            self.engine.score(self.pool[: self.tr["buckets"][-1] + 1])

    def window(self, seconds: float, prof=None) -> Window:
        import jax
        from repro.serve.engine import SERVE_STATS
        due, idx, pool = self.due, self.idx, self.pool
        tr_from = seconds * self.tr.get("trace_after", 0.3)
        tr_to = tr_from + self.tr.get("trace_seconds", 1.0)
        clock = {}
        hits0 = SERVE_STATS["hits"]
        poll = self.tr["poll_ms"] * 1e-3

        def stream():
            t0 = time.perf_counter()
            clock["t0"], clock["epoch0"] = t0, time.time()
            i, n, last = 0, len(due), -poll
            while i < n:
                now = time.perf_counter() - t0
                if prof is not None:
                    if prof.state == "idle" and now >= tr_from:
                        clock["first_batch"] = SERVE_STATS["hits"] - hits0
                        clock["trace_from"] = now
                        prof.begin()
                    elif prof.on and now >= tr_to:
                        prof.end()
                        clock["trace_to"] = now
                wake = max(due[i], last + poll)
                if wake > now:
                    if prof is not None and prof.on:
                        with jax.profiler.TraceAnnotation("bench.wait_arrivals"):
                            time.sleep(wake - now)
                    else:
                        time.sleep(wake - now)
                    now = time.perf_counter() - t0
                last = now
                j = max(int(np.searchsorted(due, now, side="right")), i + 1)
                yield pool[idx[i:j]]
                i = j

        with common.precision(self.cfg):
            rep = self.engine.score_stream(stream())
        if prof is not None:
            prof.end()
            clock.setdefault("trace_to", time.perf_counter() - clock["t0"])
        self.scores = rep.scores
        sizes = np.asarray(rep.batch_sizes)
        done = clock["t0"] + np.cumsum(np.asarray(rep.batch_walls_s))
        batch_of = np.repeat(np.arange(len(sizes)), sizes)
        n = len(due)
        lat = np.full(n, np.inf)
        m = min(n, len(batch_of))
        lat[:m] = done[batch_of[:m]] - (clock["t0"] + due[:m])
        traced = {}
        if prof is not None and "first_batch" in clock:
            first = clock["first_batch"]
            n_disp = sum(1 for s in prof.spans if s.name == "serve.dispatch")
            sel = (due >= clock["trace_from"]) & (due < clock["trace_to"])
            traced = {"first_traced_batch": first,
                      "due_epoch": (clock["epoch0"] + due[sel]).tolist(),
                      "batch_of": batch_of[:n][sel[:m]].tolist(),
                      "windows": int(sizes[first:first + n_disp].sum()),
                      "views": 2, "hidden": self.cfg["hidden"],
                      "feature_shape": list(self.meta.feature_shape)}
        return Window(attempted=n, failed=n - m,
                      e2e={"score_p95_ms": float(np.percentile(lat, 95)) * 1e3},
                      traced=traced)

    def free(self):
        import gc
        self.engine = None
        self.params = None
        gc.collect()

    # -- the check --------------------------------------------------------

    def sample(self) -> np.ndarray:
        """Positions in the stream of the windows the check compares."""
        n = min(len(self.due), 0 if self.scores is None else len(self.scores))
        k = min(n, self.tr["check_windows"])
        rng = np.random.default_rng(gen.seed32(self.cell.seed) ^ 0xC0FFEE)
        return np.sort(rng.choice(n, size=k, replace=False))

    def reference_scores(self, pos: np.ndarray, mode: str) -> np.ndarray:
        import jax
        import jax.numpy as jnp
        model = self.cell.ref_module().build(
            {"n_features": self.meta.n_features, "hidden": self.cfg["hidden"],
             "feature_shape": self.meta.feature_shape})
        params = model.init(jax.random.key(np.uint32(gen.seed32(self.cell.seed))))
        x = self.pool[self.idx[pos]]
        rows = self.tr["buckets"][-1]
        pad = (-len(x)) % rows
        xb = jnp.asarray(np.concatenate([x, np.zeros((pad, x.shape[1]),
                                                     x.dtype)]))
        f = jax.jit(lambda p, xx: jax.lax.map(
            lambda b: jax.nn.softmax(model.score_logits(p, b, mode), -1)[:, 1],
            xx.reshape(-1, rows, xx.shape[1])))
        return np.asarray(f(params, xb)).reshape(-1)[: len(x)]

    def check(self):
        pos = self.sample()
        if not len(pos):
            raise RuntimeError("no window was scored")
        ref = self.reference_scores(pos, common.ref_mode(self.cfg))
        nums = score_gaps(self.scores[pos], ref)
        limits = common.limits(self.cell.name)
        return [(k, nums[k], limits[k]) for k in limits]

    # -- readings for the limits (bench/control.py) -----------------------

    def program_readings(self) -> Dict[str, float]:
        """The program's numbers on the sampled windows of the cell's stream,
        scored back to back through the same engine (no schedule)."""
        self.scores = np.zeros(len(self.due), np.float32)
        pos = self.sample()
        with common.precision(self.cfg):
            self.engine.warmup()
            self.scores[pos] = self.engine.score(self.pool[self.idx[pos]])
        ref = self.reference_scores(pos, common.ref_mode(self.cfg))
        return score_gaps(self.scores[pos], ref)

    def control_readings(self, mode: str) -> Dict[str, float]:
        n = len(self.due)
        self.scores = np.zeros(n, np.float32)
        pos = self.sample()
        ref = self.reference_scores(pos, common.ref_mode(self.cfg))
        ctl = self.reference_scores(pos, mode)
        return score_gaps(ctl, ref)


def score_gaps(scores, ref) -> Dict[str, float]:
    """The widest and the median gap between scores and the reference's.
    The widest catches a wrong answer anywhere in the sample; the median
    separates a lower precision, whose error is spread over every window,
    from the rounding of the chunked scan, which is largest in a few."""
    gap = np.abs(np.asarray(scores, np.float64) - np.asarray(ref, np.float64))
    return {"score_gap": float(np.max(gap)),
            "score_gap_median": float(np.median(gap))}
