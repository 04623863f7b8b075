"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None) -> jnp.ndarray:
    """q: [B,S,HQ,D]; k,v: [B,T,HKV,D] -> [B,S,HQ,D] (f32 math)."""
    b, s, hq, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, s, hkv, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qf, k.astype(jnp.float32)) / math.sqrt(d)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= j <= i + (t - s)
    if window is not None:
        mask &= j > i + (t - s) - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, hq, d).astype(q.dtype)


def flash_decode_ref(q, k, v, length) -> jnp.ndarray:
    """One-token decode.  q: [B,HQ,D]; k,v: [B,T,HKV,D]; length: [] or [B]
    number of valid cache positions.  Returns [B,HQ,D]."""
    b, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d)
    scores = jnp.einsum("bkgd,btkd->bkgt", qf, k.astype(jnp.float32)) / math.sqrt(d)
    valid = jnp.arange(t)[None] < jnp.broadcast_to(jnp.asarray(length), (b,))[:, None]
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def dp_clip_noise_ref(x, noise_unit, clip: float, sigma: float,
                      norm: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Fused clip-to-norm + add sigma-scaled noise oracle.

    x: [N] f32 flat update; noise_unit: [N] standard normal; clip: L2 bound.
    """
    if norm is None:
        norm = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return (x.astype(jnp.float32) * scale + sigma * noise_unit).astype(x.dtype)


def dp_clip_noise_tree_ref(tree, key, clip: float, sigma: float):
    """Pure-jnp tree fallback with the SAME contract as
    ``kernels.ops.dp_clip_noise_tree``: shared global norm across leaves,
    one noise key per leaf (split order = leaf order).  This is the CPU
    fallback the FL aggregation path uses when no TPU is attached.

    Returns (noised_tree, pre_clip_global_norm).
    """
    leaves, treedef = jax.tree.flatten(tree)
    norm = jnp.sqrt(
        sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in leaves)
    )
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    with jax.named_scope("dp_noise"):
        keys = jax.random.split(key, len(leaves))

    def noised(l, k):
        with jax.named_scope("dp_noise"):       # as kernels/ops.py draws it
            noise = jax.random.normal(k, l.shape, jnp.float32)
        return (l.astype(jnp.float32) * scale + sigma * noise).astype(l.dtype)

    out = [noised(l, k) for l, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, out), norm


def rglru_scan_ref(a, x, h0=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential-oracle linear recurrence h_t = a_t·h_{t-1} + x_t.

    a, x: [B,L,W] f32; h0: [B,W] or None.  Returns (h [B,L,W], h_last)."""
    b, l, w = a.shape
    h0 = jnp.zeros((b, w), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        at, xt = inp
        h = at * h + xt
        return h, h

    _, hs = jax.lax.scan(step, h0, (a.transpose(1, 0, 2), x.transpose(1, 0, 2)))
    hs = hs.transpose(1, 0, 2)
    return hs, hs[:, -1]
