"""Plain reference of the ``mlp_unsw`` detector: the paper's feed-forward
network n_features -> hidden -> hidden/2 -> 2 with ReLU (Marfo et al.
2022, the paper's ref [1]), float32.  Weights are drawn from the key as
N(0, 1/fan_in) with zero biases, three keys split from the one given, in
layer order."""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp

from bench.ref.prec import einsum


def build(meta):
    d, hidden, c = meta["n_features"], meta["hidden"], meta.get("n_classes", 2)

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)

        def lin(k, a, b):
            return {"w": jax.random.normal(k, (a, b), jnp.float32) / jnp.sqrt(a),
                    "b": jnp.zeros((b,), jnp.float32)}

        return {"l1": lin(k1, d, hidden), "l2": lin(k2, hidden, hidden // 2),
                "out": lin(k3, hidden // 2, c)}

    def logits(params, x, mode="highest"):
        h = jax.nn.relu(einsum("bi,io->bo", x, params["l1"]["w"], mode)
                        + params["l1"]["b"])
        h = jax.nn.relu(einsum("bi,io->bo", h, params["l2"]["w"], mode)
                        + params["l2"]["b"])
        return (einsum("bi,io->bo", h, params["out"]["w"], mode)
                + params["out"]["b"])

    return SimpleNamespace(init=init, logits=logits, score_logits=logits)
