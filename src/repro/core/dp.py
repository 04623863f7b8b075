"""Differential privacy for FL model updates (paper §IV, "Incorporating
differential privacy in FL").

Two modes:

* ``paper``   — the mechanism exactly as written in the paper:
                ``∇w ← ∇w + N(0, σ²)`` with a fixed, user-chosen σ
                ("calibrated to the privacy budget ε" via
                :func:`gaussian_sigma` with the stated sensitivity).
                Note: without clipping the sensitivity is unbounded, so this
                is only (ε, δ)-DP under an *assumed* bound — we reproduce it
                faithfully and flag it.
* ``clipped`` — beyond-paper hardening: per-client L2 clipping of the whole
                update to S, then σ = S·sqrt(2 ln(1.25/δ))/ε (classic
                Gaussian mechanism), plus an RDP accountant for multi-round
                composition (client-level DP).

Both operate on arbitrary pytrees so every assigned architecture (dense →
400B MoE) is covered by the same code path.  The fused clip+noise Pallas
kernel in ``repro.kernels.dp_clip_noise`` implements the flat hot loop.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------


def gaussian_sigma(epsilon: float, delta: float, sensitivity: float = 1.0) -> float:
    """Classic Gaussian-mechanism noise scale for one release."""
    if epsilon <= 0:
        raise ValueError("epsilon must be > 0")
    return sensitivity * math.sqrt(2.0 * math.log(1.25 / delta)) / epsilon


def gaussian_sigma_rt(epsilon, delta: float, sensitivity=1.0):
    """Trace-safe :func:`gaussian_sigma`: ``epsilon``/``sensitivity`` may be
    traced jnp scalars (runtime FLParams inside a compiled round step);
    ``delta`` stays a static Python float so the log/sqrt fold on the host.
    No validation — callers own the ε > 0 contract."""
    return sensitivity * (math.sqrt(2.0 * math.log(1.25 / delta)) / epsilon)


# ---------------------------------------------------------------------------
# Pytree mechanics
# ---------------------------------------------------------------------------


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def clip_by_global_norm(tree, clip: float):
    """Scale the whole update so its global L2 norm is <= clip."""
    norm = global_norm(tree)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda x: (x * scale).astype(x.dtype), tree), norm


def add_gaussian_noise(tree, sigma: float, key):
    leaves, treedef = jax.tree.flatten(tree)
    with jax.named_scope("dp_noise"):
        keys = jax.random.split(key, len(leaves))

    def noised(x, k):
        with jax.named_scope("dp_noise"):
            noise = jax.random.normal(k, x.shape, jnp.float32)
        return (x + sigma * noise.astype(jnp.float32)).astype(x.dtype)

    return jax.tree.unflatten(
        treedef, [noised(x, k) for x, k in zip(leaves, keys)])


def privatize_update(tree, key, *, mode: str, clip: float, sigma: float,
                     use_kernel: Optional[bool] = None):
    """Apply the paper's DP step to one client's update pytree.

    ``use_kernel=None`` auto-routes the clipped mechanism: the fused Pallas
    clip+noise kernel when a TPU backend is attached, the jnp reference path
    on CPU (``kernels.ref.dp_clip_noise_tree_ref`` semantics — same noise
    keys, so the routing is observationally neutral).

    Returns (noised_update, pre_clip_norm).
    """
    if mode == "paper":
        norm = global_norm(tree)
        return add_gaussian_noise(tree, sigma, key), norm
    if mode == "clipped":
        from repro.kernels import backend, ops as kops

        if use_kernel is None:
            use_kernel = backend.pallas_backend_ready()
        if use_kernel:
            return kops.dp_clip_noise_tree(tree, key, clip, sigma)
        clipped, norm = clip_by_global_norm(tree, clip)
        return add_gaussian_noise(clipped, sigma, key), norm
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# RDP accountant — moved to repro.privacy.accountant (PR 3); re-exported
# here so existing call sites (`dp_lib.RdpAccountant`, ...) keep working.
# ---------------------------------------------------------------------------

from repro.privacy.accountant import (ORDERS as _ORDERS,  # noqa: E402,F401
                                      RdpAccountant, compose_epsilon,
                                      noise_multiplier_for_budget,
                                      rdp_gaussian, rdp_subsampled_gaussian,
                                      rdp_to_dp)
