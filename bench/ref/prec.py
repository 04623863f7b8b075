"""Matrix products of the plain references at a stated precision.

``highest`` is float32 throughout.  ``high`` is the TPU's three-pass
bfloat16 product (``Precision.HIGH``): each operand splits into a bfloat16
head and tail, and three of the four head/tail products are summed in
float32.  ``bf16`` is one pass on operands rounded to bfloat16, with
float32 sums (the TPU's default).  On a TPU the two lower modes are the
chip's own passes; elsewhere, where a float32 product ignores the
precision flag, they are written out with explicit roundings.  (Written
out on the TPU, the control's gaps read about 100 times those of the
chip's own passes; why was not looked into.)  The control of a check is
the reference at the precision one step below the configuration's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_NATIVE = {"high": jax.lax.Precision.HIGH, "bf16": jax.lax.Precision.DEFAULT}


def _split(a):
    head = a.astype(jnp.bfloat16).astype(jnp.float32)
    tail = (a - head).astype(jnp.bfloat16).astype(jnp.float32)
    return head, tail


def einsum(spec: str, a, b, mode: str = "highest"):
    """``jnp.einsum(spec, a, b)`` at ``mode`` (see the module doc)."""
    if mode == "highest":
        return jnp.einsum(spec, a, b, precision=_HI)
    if jax.default_backend() == "tpu" and mode in _NATIVE:
        return jnp.einsum(spec, a, b, precision=_NATIVE[mode])
    if mode == "high":
        ah, at = _split(a)
        bh, bt = _split(b)
        return (jnp.einsum(spec, ah, bh, precision=_HI)
                + jnp.einsum(spec, ah, bt, precision=_HI)
                + jnp.einsum(spec, at, bh, precision=_HI))
    if mode == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    raise ValueError(f"unknown precision mode {mode!r}")


def lower(mode: str) -> str:
    """The precision one step below ``mode``: the control's."""
    return {"highest": "high", "high": "bf16"}[mode]
