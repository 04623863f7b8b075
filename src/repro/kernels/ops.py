"""Jit'd public wrappers over the Pallas kernels (stable API for the model
zoo and the FL core).  Each function dispatches to the kernel and is
validated against ``repro.kernels.ref`` in tests/test_kernels.py."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import backend
from repro.kernels import dp_clip_noise as _dp
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import ref as _ref
from repro.kernels import rglru_scan as _rg


def default_route() -> str:
    """Score-path kernel route for the serving engine (repro/serve): the
    Pallas kernels (``"kernel"``) when the backend can compile them, the
    pure-jnp ``kernels.ref`` fallback (``"ref"``) elsewhere — the same
    by-backend dispatch the DP clip+noise aggregation path uses."""
    return "kernel" if backend.pallas_backend_ready() else "ref"


def per_device(fn):
    """``fn`` made legal inside a program that XLA partitions over a mesh.

    XLA cannot partition a Mosaic kernel, so under an ambient mesh whose
    axes are not all manual already, the call runs in a ``shard_map`` over
    that mesh.  Its specs name no axis: a mesh axis the caller batches over
    (``jax.vmap(..., spmd_axis_name="lane")``, as both FL runners do) is
    split by the batching rule, so each device gets its own lanes'
    operands; any other axis (the population's ``client``) sees them
    replicated.  With no mesh, one device, or every axis manual already,
    ``fn`` is returned as is."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or \
            set(mesh.manual_axes) == set(mesh.axis_names):
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """``interpret=None`` (default) auto-routes by backend: compiled Pallas
    on TPU, interpret mode elsewhere (``backend.resolve_interpret``)."""
    return per_device(lambda q, k, v: _fa.flash_attention(
        q, k, v, causal=causal, window=window, interpret=interpret))(q, k, v)


def flash_decode(q, k, v, length, *, interpret: Optional[bool] = None,
                 return_partials: bool = False):
    """``interpret=None`` (default) auto-routes by backend — the kernel is
    never silently interpreted on real hardware."""
    return per_device(lambda q, k, v, length: _fd.flash_decode(
        q, k, v, length, interpret=interpret,
        return_partials=return_partials))(q, k, v, length)


combine_decode_partials = _fd.combine_partials


def rglru_scan(a, x, h0=None, *, interpret: Optional[bool] = None):
    """``interpret=None`` (default) auto-routes by backend: compiled Pallas
    on TPU, interpret mode elsewhere — same resolve as the flash kernels.
    The sequential chunked scan is bitwise-equal to
    ``kernels.ref.rglru_scan_ref`` (asserted in tests/test_kernels.py), so
    the ``"kernel"``/``"ref"`` score routes of the sequence detectors agree
    to the bit."""
    return per_device(lambda a, x, h0: _rg.rglru_scan(
        a, x, h0, interpret=interpret))(a, x, h0)


def dp_clip_noise(x, noise, clip: float, sigma: float, *,
                  interpret: Optional[bool] = None):
    return _dp.dp_clip_noise(x, noise, clip, sigma, interpret=interpret)


def dp_clip_noise_tree(tree, key, clip: float, sigma: float, *,
                       interpret: Optional[bool] = None):
    """Pytree version with a SHARED global norm (client-level DP contract —
    identical semantics to core.dp.privatize_update(mode='clipped')).

    ``interpret=None`` auto-routes: compiled Pallas when the backend is TPU,
    the ``kernels.ref`` pure-jnp fallback on CPU (same key-split order, so
    both paths produce bit-identical noise).  Pass ``interpret=True`` to
    force interpret-mode Pallas (kernel validation on CPU).

    ``clip``/``sigma`` may be traced scalars (runtime FLParams — the engine
    sweeps them without recompiling).  The Pallas kernel bakes ``sigma`` as
    a compile-time constant, so a traced sigma is folded into the noise
    operand instead (``x·scale + 1.0·(σ·n)`` — same f32 product, one extra
    elementwise multiply outside the fused pass).  This fold is what lets
    the privacy subsystem's budget schedulers (``repro/privacy``) drive a
    *per-round* σ_t through the fused kernel: scheduler output arrives here
    as ``FLParams.dp_sigma``, a traced value like any other lane.

    Returns (noised_tree, pre_clip_global_norm)."""
    if interpret is None:
        if not backend.pallas_backend_ready():
            return _ref.dp_clip_noise_tree_ref(tree, key, clip, sigma)
        interpret = False
    if isinstance(sigma, (int, float)):   # a static sigma stays static
        return per_device(lambda tree, key, clip: _dp_tree(
            tree, key, clip, sigma, interpret))(tree, key, clip)
    return per_device(lambda tree, key, clip, sigma: _dp_tree(
        tree, key, clip, sigma, interpret))(tree, key, clip, sigma)


def _dp_tree(tree, key, clip, sigma, interpret: bool):
    leaves, treedef = jax.tree.flatten(tree)
    total = sum(_dp.sumsq(l, interpret=interpret) for l in leaves)
    norm = jnp.sqrt(total)
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    sigma_static = isinstance(sigma, (int, float))
    noise = _dp_noise(key, tuple(l.shape for l in leaves),
                      None if sigma_static else sigma)
    out = [
        _dp.scale_noise(leaf, n, scale,
                        sigma=float(sigma) if sigma_static else 1.0,
                        interpret=interpret)
        for leaf, n in zip(leaves, noise)
    ]
    return jax.tree.unflatten(treedef, out), norm


@functools.partial(jax.jit, static_argnames=("shapes",))
def _dp_noise(key, shapes, sigma):
    """One standard-normal draw per leaf (key-split order = leaf order),
    times ``sigma`` unless it is None (a static sigma is baked into the
    kernel instead).  Jitted so that ``dp_noise`` stays one component of the
    ops' scope path under the engine's client ``vmap``."""
    with jax.named_scope("dp_noise"):
        keys = jax.random.split(key, len(shapes))
        noise = [jax.random.normal(k, s, jnp.float32)
                 for k, s in zip(keys, shapes)]
        return noise if sigma is None else [sigma * n for n in noise]
