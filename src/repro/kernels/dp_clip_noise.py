"""Fused DP clip+noise as Pallas TPU kernels (the paper's hot DP step).

The (ε, δ) mechanism touches every byte of every client update — for a 123B
model that is ~0.5 TB of HBM traffic per round.  Fusing the clip-scale and
noise-add into one tiled pass bounds traffic at 2 reads + 1 write per
element; the global-norm reduction is a separate single-read pass (needed
before any scaling can happen).

Two kernels:
  * ``sumsq``      — tiled Σx² reduction (SMEM scalar accumulated across the
                     sequential TPU grid).
  * ``scale_noise``— o = x·scale + σ·n elementwise over [bt, 128] VMEM tiles.

``interpret=None`` (every entry point's default) resolves by backend
(:func:`repro.kernels.backend.resolve_interpret`): compiled Mosaic on
TPU, interpret mode elsewhere.

Named scopes split the step on a device trace: ``dp_layout`` is the pad to
[rows, 128] tiles and back, ``dp_kernel`` the Pallas calls (named
``dp_sumsq`` and ``dp_scale_noise``); ``kernels/ops.py`` adds ``dp_noise``
around the noise draw.

NOTE: validation runs in interpret mode on CPU where ``pltpu.prng_*`` has
no lowering, so standard-normal noise is an explicit operand here.  On real
TPU the noise read can be removed by seeding ``pltpu.prng_seed`` per tile and
box-mullering ``prng_random_bits`` in-register — same contract, one fewer
operand; see EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

LANES = 128


def _sumsq_kernel(x_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[0, 0] = jnp.zeros((), jnp.float32)

    x = x_ref[...].astype(jnp.float32)
    o_ref[0, 0] += jnp.sum(x * x)


def _scale_noise_kernel(scale_ref, x_ref, n_ref, o_ref, *, sigma: float):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = (x * scale_ref[...] + sigma * n_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype
    )


def _pad_2d(x, bt: int):
    """``x`` flattened and zero-padded to [rows, 128] with rows a multiple
    of ``bt``."""
    with jax.named_scope("dp_layout"):
        n = x.size
        rows = -(-n // LANES)
        rows_pad = -(-rows // bt) * bt
        flat = jnp.zeros((rows_pad * LANES,),
                         x.dtype).at[:n].set(x.reshape(-1))
        return flat.reshape(rows_pad, LANES), rows_pad


def sumsq(x, *, bt: int = 256,
          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Σ x² over every element of ``x``, tiled [bt, 128] (zero-padded)."""
    return _sumsq(x, bt=bt, interpret=backend.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def _sumsq(x, *, bt: int, interpret: bool) -> jnp.ndarray:
    x2d, rows = _pad_2d(x, bt)
    with jax.named_scope("dp_kernel"):
        out = pl.pallas_call(
            _sumsq_kernel,
            grid=(rows // bt,),
            in_specs=[pl.BlockSpec((bt, LANES), lambda i: (i, 0))],
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
            interpret=interpret,
            name="dp_sumsq",
        )(x2d)
        return out[0, 0]


def scale_noise(x, noise, scale, *, sigma: float, bt: int = 256,
                interpret: Optional[bool] = None):
    """o = x·scale + σ·noise (elementwise, shape preserved)."""
    return _scale_noise(x, noise, scale, sigma=sigma, bt=bt,
                        interpret=backend.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("sigma", "bt", "interpret"))
def _scale_noise(x, noise, scale, *, sigma: float, bt: int, interpret: bool):
    shape, n = x.shape, x.size
    x2d, rows = _pad_2d(x, bt)
    n2d, _ = _pad_2d(noise, bt)
    with jax.named_scope("dp_kernel"):
        # ``scale`` is a (1, 1) VMEM block equal to its whole array, so it
        # stays legal under the (8, 128) tiling rule once the engine vmaps
        # the call over clients (a whole-array SMEM block does not).
        scale_arr = jnp.asarray(scale, jnp.float32).reshape(1, 1)
        out = pl.pallas_call(
            functools.partial(_scale_noise_kernel, sigma=float(sigma)),
            grid=(rows // bt,),
            in_specs=[
                pl.BlockSpec((1, 1), lambda i: (0, 0)),
                pl.BlockSpec((bt, LANES), lambda i: (i, 0)),
                pl.BlockSpec((bt, LANES), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bt, LANES), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((rows, LANES), x.dtype),
            interpret=interpret,
            name="dp_scale_noise",
        )(scale_arr, x2d, n2d)
    with jax.named_scope("dp_layout"):
        return out.reshape(-1)[:n].reshape(shape)


def dp_clip_noise(x, noise, clip: float, sigma: float, *, bt: int = 256,
                  interpret: Optional[bool] = None):
    """Full fused mechanism on one flat array: clip to L2 ``clip``, add
    σ-scaled standard-normal ``noise``.  Matches ``ref.dp_clip_noise_ref``."""
    norm = jnp.sqrt(sumsq(x, bt=bt, interpret=interpret))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return scale_noise(x, noise, scale, sigma=sigma, bt=bt, interpret=interpret), norm
