"""The work counters, tied to the program's own model shapes."""
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.work import flops as work
from repro.models.spec import DataMeta, get_model_spec


def _n_params(spec):
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))


def test_mlp_params_and_flops_match_the_spec():
    spec = get_model_spec("mlp", DataMeta(42, 2, 128, (42,)))
    assert work.mlp_params(42, 128) == _n_params(spec) == 13_890
    x = jax.ShapeDtypeStruct((64, 42), jnp.float32)
    p = jax.eval_shape(spec.init, jax.random.key(0))
    cost = jax.jit(spec.logits).lower(p, x).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    # the matmuls; XLA adds the bias adds and the ReLUs on top
    assert 64 * work.mlp_fwd_flops(42, 128) <= cost["flops"] \
        <= 1.05 * 64 * work.mlp_fwd_flops(42, 128)


def test_ssm_params_and_scan_width_match_the_spec():
    meta = DataMeta(384, 2, 64, (64, 6))
    spec = get_model_spec("ssm", meta)
    assert work.ssm_params(64, 64, 6) == _n_params(spec)
    p = jax.eval_shape(spec.init, jax.random.key(0))["mix"]
    g = work.ssm_dims(64, 64, 6)
    heads, d_in = p["A_log"].shape[0], p["norm_scale"].shape[0]
    n = (p["conv_w"].shape[1] - d_in) // 2
    assert (g["heads"], g["d_in"], g["n"]) == (heads, d_in, n)
    assert p["in_proj"].shape == (g["d"], 2 * d_in + 2 * n + heads)
    # the routed scan runs over [batch, chunks, heads·p·n] states
    fl, by = work.rglru_scan_work(128, g["nc"], heads * (d_in // heads) * n)
    assert g["nc"] == 4 and fl == 2 * 128 * 4 * 512
    assert by == 4 * (3 * 128 * 4 * 512 + 2 * 128 * 512)


def test_dp_work_counts_the_cohort_only():
    n = work.mlp_params(42, 128)
    assert work.dp_bytes(8, n) == 8 * 8 * n
    assert work.dp_bytes(2 * 8, n) == 2 * work.dp_bytes(8, n)
    meta = {"n_features": 42, "hidden": 128, "local_steps": 5,
            "local_batch": 64, "n_test": 100}
    # one round of 8 clients plus one eval block
    want = 8 * 5 * 64 * 3 * work.mlp_fwd_flops(42, 128) \
        + 100 * work.mlp_fwd_flops(42, 128)
    assert work.train_flops("mlp", meta, 8, 1) == want


def test_ssm_flops_scale_with_the_window():
    a = work.ssm_view_flops(64, 64, 6)
    b = work.ssm_view_flops(64, 128, 6)
    assert 1.9 * a < b < 2.1 * a
    assert work.fwd_flops("ssm", {"hidden": 64, "feature_shape": [64, 6]}) == a
