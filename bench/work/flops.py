"""Work the algorithms need, counted from shapes: model FLOPs per sample,
parameter counts, and the bytes of the DP step and of the RG-LRU scan.

Counted work is the algorithm's: a matrix product of [m, k] by [k, n] is
2·m·k·n FLOPs; elementwise activations, norms and softmaxes are left out
(they are a few percent of these models' matmul FLOPs).  A backward pass is
counted as twice the forward.
"""
from __future__ import annotations

from typing import Dict, Sequence


def mlp_params(n_features: int, hidden: int, n_classes: int = 2) -> int:
    h2 = hidden // 2
    return (n_features * hidden + hidden + hidden * h2 + h2
            + h2 * n_classes + n_classes)


def mlp_fwd_flops(n_features: int, hidden: int, n_classes: int = 2) -> int:
    h2 = hidden // 2
    return 2 * (n_features * hidden + hidden * h2 + h2 * n_classes)


def ssm_dims(hidden: int, window: int, n_signals: int) -> Dict[str, int]:
    """The ``ssm`` detector's widths as the program derives them from the
    width knob: d_model = max(16, hidden/4), expansion 2, 2 heads of
    d_model, state 16, chunk 16 (or the largest power of two dividing the
    window), causal conv of 4."""
    d = max(16, hidden // 4)
    chunk = next(c for c in (16, 8, 4, 2, 1) if window % c == 0)
    return {"d": d, "d_in": 2 * d, "heads": 2, "p": d, "n": 16,
            "chunk": chunk, "conv": 4, "L": window, "S": n_signals,
            "nc": window // chunk}


def ssm_params(hidden: int, window: int, n_signals: int,
               n_classes: int = 2) -> int:
    g = ssm_dims(hidden, window, n_signals)
    d, di, h, n = g["d"], g["d_in"], g["heads"], g["n"]
    d_proj = 2 * di + 2 * n + h
    mix = (d * d_proj + g["conv"] * (di + 2 * n) + (di + 2 * n)
           + 3 * h + di + di * d)
    return n_signals * d + d + mix + 3 * d * n_classes + n_classes


def ssm_view_flops(hidden: int, window: int, n_signals: int,
                   n_classes: int = 2) -> int:
    """One view of one window through the chunked SSD block."""
    g = ssm_dims(hidden, window, n_signals)
    L, S, d, di, h, p, n = (g[k] for k in ("L", "S", "d", "d_in", "heads",
                                           "p", "n"))
    q, nc, k = g["chunk"], g["nc"], g["conv"]
    d_proj = 2 * di + 2 * n + h
    return (2 * L * S * d                 # embed
            + 2 * L * d * d_proj          # fused input projection
            + 2 * k * L * (di + 2 * n)    # depthwise causal conv
            + 2 * nc * q * q * n          # C·B within chunks
            + 2 * nc * h * q * q * p      # intra-chunk output
            + 2 * nc * q * h * p * n      # chunk states
            + 2 * nc * h * p * n          # inter-chunk recurrence
            + 2 * nc * q * h * p * n      # inter-chunk output
            + 2 * L * di * d              # output projection
            + 2 * 3 * d * n_classes)      # head on mean/last/max pooling


def fwd_flops(model: str, meta: Dict) -> int:
    """Forward FLOPs of one sample in the training view."""
    if model == "mlp":
        return mlp_fwd_flops(meta["n_features"], meta["hidden"])
    if model == "ssm":
        w, s = meta["feature_shape"]
        return ssm_view_flops(meta["hidden"], w, s)
    raise KeyError(model)


def params(model: str, meta: Dict) -> int:
    if model == "mlp":
        return mlp_params(meta["n_features"], meta["hidden"])
    if model == "ssm":
        w, s = meta["feature_shape"]
        return ssm_params(meta["hidden"], w, s)
    raise KeyError(model)


def train_flops(model: str, meta: Dict, cohort_rounds: float,
                eval_blocks: int) -> float:
    """Model FLOPs of the traced calls: forward and backward of the local
    steps of the clients the rounds selected, and the eval forward over
    the test set once per eval block."""
    f = fwd_flops(model, meta)
    local = cohort_rounds * meta["local_steps"] * meta["local_batch"] * 3 * f
    return local + eval_blocks * meta["n_test"] * f


def dp_bytes(cohort_rounds: float, n_params: int) -> float:
    """Clip and noise of each selected client's update: read one f32
    update, write one f32 update."""
    return 8.0 * cohort_rounds * n_params


def dp_flops(cohort_rounds: float, n_params: int) -> float:
    """Square-and-sum for the norm, then scale and add the noise."""
    return 4.0 * cohort_rounds * n_params


def rglru_scan_work(batch: int, length: int, width: int):
    """(FLOPs, bytes) of ``h_t = a_t·h_{t-1} + x_t`` over [batch, length,
    width] f32: read a and x, write h, read h0 and write the last state."""
    elems = batch * length * width
    return 2.0 * elems, 4.0 * (3 * elems + 2 * batch * width)
