"""RG-LRU linear recurrence h_t = a_t·h_{t-1} + x_t as a Pallas TPU kernel.

Grid: (batch, W/bw, L/chunk) with the *sequence-chunk axis innermost* so the
hidden state persists in VMEM scratch across chunk steps (TPU grids execute
sequentially).  Inside a chunk the recurrence is a `fori_loop` over time —
elementwise VPU work on [1, bw] rows; HBM traffic is exactly one read of
(a, x) and one write of h, which is the bandwidth floor for this op.

XLA's alternative (`associative_scan`) does O(log L) full passes over the
sequence; this kernel is the paper-agnostic beyond-XLA win for the
RecurrentGemma architecture (EXPERIMENTS.md §Perf discusses the trade-off).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend


def _kernel(h0_ref, a_ref, x_ref, h_ref, hlast_ref, state_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = h0_ref[...].astype(jnp.float32)

    # one [1, bw] row per step, read and written through the refs: Mosaic
    # has no dynamic_slice of a loaded value, only dynamic ref indexing
    def step(t, h):
        row = pl.ds(t, 1)
        h = (a_ref[0, row, :].astype(jnp.float32) * h
             + x_ref[0, row, :].astype(jnp.float32))
        h_ref[0, row, :] = h.astype(h_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, state_scr[0])
    state_scr[0] = h

    @pl.when(ci == nc - 1)
    def _finish():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def rglru_scan(a, x, h0=None, *, chunk: int = 128, bw: int = 512,
               interpret: Optional[bool] = None):
    """a, x: [B, L, W]; h0: [B, W] or None -> (h [B,L,W], h_last [B,W]).

    ``interpret=None`` resolves by backend
    (:func:`repro.kernels.backend.resolve_interpret`)."""
    return _rglru_scan(a, x, h0, chunk=chunk, bw=bw,
                       interpret=backend.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "bw", "interpret"))
def _rglru_scan(a, x, h0, *, chunk: int, bw: int, interpret: bool):
    b, l, w = a.shape
    chunk = min(chunk, l)
    bw = min(bw, w)
    assert l % chunk == 0 and w % bw == 0, (l, chunk, w, bw)
    if h0 is None:
        h0 = jnp.zeros((b, w), jnp.float32)

    # h0 / h_last travel as [B, 1, W] with (1, 1, bw) blocks: the last two
    # block dims then equal the array's (1) or tile its lanes (bw), which
    # the TPU lowering needs for any B (a (1, bw) block on [B, W] is legal
    # only when B == 1).
    grid = (b, w // bw, l // chunk)
    with jax.named_scope("rglru_scan"):
        h, hlast = pl.pallas_call(
            functools.partial(_kernel, chunk=chunk),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bw), lambda bi, wi, ci: (bi, 0, wi)),
                pl.BlockSpec((1, chunk, bw), lambda bi, wi, ci: (bi, ci, wi)),
                pl.BlockSpec((1, chunk, bw), lambda bi, wi, ci: (bi, ci, wi)),
            ],
            out_specs=[
                pl.BlockSpec((1, chunk, bw), lambda bi, wi, ci: (bi, ci, wi)),
                pl.BlockSpec((1, 1, bw), lambda bi, wi, ci: (bi, 0, wi)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, l, w), jnp.float32),
                jax.ShapeDtypeStruct((b, 1, w), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((1, 1, bw), jnp.float32)],
            interpret=interpret,
            name="rglru_scan",
        )(h0.astype(jnp.float32).reshape(b, 1, w), a, x)
    return h, hlast.reshape(b, w)
