"""Plain reference of the ``ssm_road`` detector: a Mamba-2 block
(arXiv:2405.21060) over a raw CAN window, float32, in its recurrent form.

Per window [L, S] (time-major): embed the S signals to d_model; fused
input projection to (z, x, B, C, dt); depthwise causal conv of width 4
with SiLU over (x, B, C); per head the state recurrence
``S_t = exp(-A·dt_t)·S_{t-1} + dt_t·x_t⊗B_t`` and ``y_t = S_t·C_t + D·x_t``;
gate by SiLU(z); RMS norm (eps 1e-6) with a learned scale; output
projection; residual; mean, last and max over time; a linear head.  The
score averages the logits of the window and of the window rolled by L/2
in time.  The program computes the same recurrence in its chunked
(state-space dual) form; this reference steps through time one position
at a time.

Sizes come from the width knob ``hidden``: d_model = max(16, hidden/4),
inner width 2·d_model in 2 heads of d_model, state 16.  Weights are drawn
from the key as N(0, 1/fan_in) (the conv's fan-in is its width), biases
zero, A = 1..16 spaced evenly over the heads (log-parametrised), D = 1,
dt bias -2, norm scale 1.
"""
from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from bench.ref.prec import einsum

N_STATE = 16
CONV = 4


def build(meta):
    window, n_sig = meta["feature_shape"]
    c = meta.get("n_classes", 2)
    d = max(16, meta["hidden"] // 4)
    d_in, heads, p, n = 2 * d, 2, d, N_STATE
    d_proj = 2 * d_in + 2 * n + heads

    def normal(k, shape, fan_in):
        return (1.0 / math.sqrt(fan_in)) * jax.random.normal(k, shape, jnp.float32)

    def init(key):
        k_embed, k_head, k_mix = jax.random.split(key, 3)
        m1, m2, _, m4 = jax.random.split(k_mix, 4)
        mix = {
            "in_proj": normal(m1, (d, d_proj), d),
            "conv_w": normal(m2, (CONV, d_in + 2 * n), CONV),
            "conv_b": jnp.zeros((d_in + 2 * n,), jnp.float32),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, heads).astype(jnp.float32)),
            "D": jnp.ones((heads,), jnp.float32),
            "dt_bias": jnp.full((heads,), -2.0, jnp.float32),
            "norm_scale": jnp.ones((d_in,), jnp.float32),
            "out_proj": normal(m4, (d_in, d), d_in),
        }
        return {"embed": {"w": normal(k_embed, (n_sig, d), n_sig),
                          "b": jnp.zeros((d,), jnp.float32)},
                "mix": mix,
                "head": {"w": normal(k_head, (3 * d, c), 3 * d),
                         "b": jnp.zeros((c,), jnp.float32)}}

    def view(params, hw, mode):
        """[b, L, S] -> logits [b, c]."""
        b = hw.shape[0]
        mx = params["mix"]
        h = einsum("bls,sd->bld", hw, params["embed"]["w"], mode) + params["embed"]["b"]
        proj = einsum("bld,de->ble", h, mx["in_proj"], mode)
        z = proj[..., :d_in]
        xbc = proj[..., d_in:2 * d_in + 2 * n]
        dt = jax.nn.softplus(proj[..., 2 * d_in + 2 * n:] + mx["dt_bias"])
        pad = jnp.concatenate([jnp.zeros((b, CONV - 1, xbc.shape[-1])), xbc], 1)
        conv = sum(pad[:, i:i + window] * mx["conv_w"][i] for i in range(CONV))
        xbc = jax.nn.silu(conv + mx["conv_b"])
        xs = xbc[..., :d_in].reshape(b, window, heads, p)
        B, C = xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
        A = jnp.exp(mx["A_log"])

        def step(s, inp):
            x_t, b_t, c_t, dt_t = inp
            s = (jnp.exp(-A * dt_t)[:, :, None, None] * s
                 + dt_t[:, :, None, None] * x_t[..., None] * b_t[:, None, None, :])
            return s, jnp.sum(s * c_t[:, None, None, :], axis=-1)

        s0 = jnp.zeros((b, heads, p, n), jnp.float32)
        _, ys = jax.lax.scan(step, s0, (xs.transpose(1, 0, 2, 3),
                                        B.transpose(1, 0, 2),
                                        C.transpose(1, 0, 2),
                                        dt.transpose(1, 0, 2)))
        y = ys.transpose(1, 0, 2, 3) + mx["D"][None, None, :, None] * xs
        y = y.reshape(b, window, d_in) * jax.nn.silu(z)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6)
        y = y * mx["norm_scale"]
        h = h + einsum("ble,ed->bld", y, mx["out_proj"], mode)
        pooled = jnp.concatenate([h.mean(1), h[:, -1], h.max(1)], -1)
        return einsum("bi,io->bo", pooled, params["head"]["w"], mode) + params["head"]["b"]

    def unflat(x):
        return x.reshape(x.shape[0], window, n_sig)

    def logits(params, x, mode="highest"):
        """Training view: the window as it is."""
        return view(params, unflat(x), mode)

    def score_logits(params, x, mode="highest"):
        """Scoring view: mean over the window and its roll by L/2."""
        hw = unflat(x)
        return (view(params, hw, mode)
                + view(params, jnp.roll(hw, window // 2, axis=1), mode)) / 2

    return SimpleNamespace(init=init, logits=logits, score_logits=score_logits)
