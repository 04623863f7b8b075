"""Pieces the drivers share: the program's data classes built from the
benchmark's generated data, lane seeds, precision, and the comparison of
numbers between a run and the reference."""
from __future__ import annotations

import contextlib
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.gen import data as gen


def precision(cfg: Dict):
    """The matmul precision the configuration states, as a context."""
    import jax
    mode = cfg.get("matmul_precision", "default")
    if mode == "default":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(mode)


def ref_mode(cfg: Dict) -> str:
    """The reference's precision mode for the configuration (float32)."""
    return "highest"


def lane_seeds(seed: int, call: int, per_call: int) -> List[int]:
    """Distinct 32-bit lane seeds of call ``call``: consecutive from the
    run seed, so that no two calls of a run share a lane."""
    base = gen.seed32(seed)
    return [(base + call * per_call + j) % (1 << 32) for j in range(per_call)]


def program_federation(fd: gen.Federation):
    """The program's ``FederatedData`` holding the generated shards."""
    from repro.data.synthetic import FederatedData
    shape = fd.feature_shape if len(fd.feature_shape) > 1 else None
    return FederatedData(x=list(fd.x), y=list(fd.y), test_x=fd.test_x,
                         test_y=fd.test_y, n_features=fd.n_features,
                         n_classes=2, feature_shape=shape)


def reference_stack(fd: gen.Federation) -> Dict[str, np.ndarray]:
    """Padded shards, sizes, normalised sizes and label entropy, as the
    reference reads them."""
    n = len(fd.x)
    max_n = max(len(x) for x in fd.x)
    xs = np.zeros((n, max_n, fd.n_features), np.float32)
    ys = np.zeros((n, max_n), np.int32)
    for i, (x, y) in enumerate(zip(fd.x, fd.y)):
        xs[i, :len(x)] = x
        ys[i, :len(y)] = y
    sizes = np.array([len(x) for x in fd.x], np.float32)
    return {"x": xs, "y": ys, "sizes": sizes.astype(np.int32),
            "weight": sizes / sizes.mean(),
            "quality": gen.label_entropy(fd.y),
            "test_x": fd.test_x, "test_y": fd.test_y}


def gap_numbers(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
                ) -> Dict[str, float]:
    """Per-lane gaps between a run's first-block history and the
    reference's, reduced over lanes by the median.

    Only the first eval block is compared, and by the median over lanes:
    the top-K selection is a discrete function of loss-driven utilities,
    so a rounding difference that reorders two clients' scores sends a
    lane onto another trajectory.  At float32 that happens to a lane now
    and then; a fault or a lower precision moves every lane.  Lanes whose
    reference loss is not finite (training diverged under the DP noise,
    as the eps = 0.1 lanes can) are left out of the medians; a lane that
    is finite on one side only counts in ``nonfinite_lanes``."""
    lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
    fin_p, fin_r = np.isfinite(lp), np.isfinite(lr)
    both = fin_p & fin_r
    out = {"nonfinite_lanes": float(np.sum(fin_p != fin_r))}
    if not both.any():
        out.update(loss_gap=float("nan"), acc_gap=float("nan"),
                   auc_gap=float("nan"))
        return out
    rel = np.abs(lp - lr)[both] / np.maximum(np.abs(lr[both]), 1e-6)
    out["loss_gap"] = float(np.median(rel))
    for key in ("acc", "auc"):
        d = np.abs(np.asarray(prog[key], np.float64)
                   - np.asarray(ref[key], np.float64))[both]
        out[f"{key}_gap"] = float(np.median(d))
    out["compared_lanes"] = float(both.sum())
    return out


def limits(workload: str) -> Dict[str, float]:
    """The cell's comparison limits (``bench/limits/<workload>.json``):
    each number the check compares, with the largest value it may read."""
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "limits", f"{workload}.json")
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}
