"""Compiled-engine tests (ISSUE 1): the scan/vmap engine must be a drop-in
replacement for the legacy per-round Python loop.

* scanned single-seed ``run_fl`` matches the legacy loop's final accuracy
  within ±0.02 and its ε exactly (same accountant over the same rounds);
* ``run_fl_batch`` over 3 seeds matches 3 sequential scanned runs lane for
  lane (vmap must not change semantics);
* the jit-safe time model is jit-invariant and ordering-sane;
* DP routing (Pallas kernel vs kernels/ref fallback) is observationally
  neutral inside ``privatize_update``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import dp as dp_lib
from repro.data.synthetic import (make_federated, sample_round_batches,
                                  stack_federation)
from repro.train import fl_driver

ROUNDS = 30
EVAL_EVERY = 5


@pytest.fixture(scope="module")
def fed():
    return make_federated(0, "unsw", n_samples=2_000, n_clients=10)


@pytest.fixture(scope="module")
def fl():
    return FLConfig(n_clients=10, clients_per_round=4, rounds=ROUNDS,
                    local_epochs=3, local_batch=32, local_lr=0.08,
                    dp_enabled=True, dp_mode="clipped", dp_epsilon=200.0,
                    dp_clip=5.0, fault_tolerance=True, failure_prob=0.05)


# ---------------------------------------------------------------------------
# scan engine vs legacy loop
# ---------------------------------------------------------------------------


def test_scan_engine_matches_legacy(fed, fl):
    """The two engines draw independent batch streams (device jax.random vs
    host NumPy), so per-seed accuracy is a statistical quantity: compare the
    mean over 3 seeds at the ISSUE tolerance, ε exactly."""
    seeds = (0, 1, 2)
    legacy = [fl_driver.run_fl_legacy(fed, fl, "proposed", seed=s,
                                      rounds=ROUNDS, eval_every=EVAL_EVERY)
              for s in seeds]
    scan = fl_driver.run_fl_batch(fed, fl, "proposed", seeds=seeds,
                                  rounds=ROUNDS, eval_every=EVAL_EVERY)
    acc_l = float(np.mean([r.accuracy for r in legacy]))
    acc_s = float(np.mean([r.accuracy for r in scan]))
    assert abs(acc_s - acc_l) <= 0.02
    for l, s in zip(legacy, scan):
        assert abs(s.eps_spent - l.eps_spent) <= 1e-6
        assert s.rounds == l.rounds
        # same eval grid, same history schema
        assert s.history["round"] == l.history["round"]
        assert set(s.history) == set(l.history)
        # the simulated-time model is the same function in both engines;
        # totals differ only through which clients were selected/failed
        assert s.sim_time_s == pytest.approx(l.sim_time_s, rel=0.25)


def test_partial_eval_block_matches_legacy_grid(fed, fl):
    """rounds % eval_every != 0 exercises the trailing partial scan block;
    the eval grid must still match the legacy loop's exactly."""
    legacy = fl_driver.run_fl_legacy(fed, fl, "random", seed=1, rounds=12,
                                     eval_every=5)
    scan = fl_driver.run_fl(fed, fl, "random", seed=1, rounds=12, eval_every=5)
    assert scan.history["round"] == [5, 10, 12] == legacy.history["round"]
    assert len(scan.history["acc"]) == 3
    # cumulative time must be nondecreasing across eval points
    assert np.all(np.diff(scan.history["cum_time"]) >= 0)


def test_batch_matches_sequential_runs(fed, fl):
    seeds = (0, 3, 7)
    batch = fl_driver.run_fl_batch(fed, fl, "proposed", seeds=seeds,
                                   rounds=ROUNDS, eval_every=EVAL_EVERY)
    for seed, b in zip(seeds, batch):
        single = fl_driver.run_fl(fed, fl, "proposed", seed=seed,
                                  rounds=ROUNDS, eval_every=EVAL_EVERY)
        assert b.seed == seed
        # each vmap lane keys off jax.random.key(seed): identical math
        np.testing.assert_allclose(b.accuracy, single.accuracy, atol=1e-5)
        np.testing.assert_allclose(b.auc, single.auc, atol=1e-4)
        np.testing.assert_allclose(b.sim_time_s, single.sim_time_s, rtol=1e-5)
        np.testing.assert_allclose(b.history["acc"], single.history["acc"],
                                   atol=1e-5)
    assert b.eps_spent == pytest.approx(single.eps_spent, abs=1e-12)


def test_batch_is_deterministic_and_cached(fed, fl):
    """Same (config, seeds) -> identical results; the second call reuses the
    compiled runner (no recompile — this is what sweeps rely on)."""
    a = fl_driver.run_fl_batch(fed, fl, "proposed", seeds=(0, 3, 7),
                               rounds=ROUNDS, eval_every=EVAL_EVERY)
    n_cached = len(fl_driver._RUNNER_CACHE)
    b = fl_driver.run_fl_batch(fed, fl, "proposed", seeds=(0, 3, 7),
                               rounds=ROUNDS, eval_every=EVAL_EVERY)
    assert len(fl_driver._RUNNER_CACHE) == n_cached
    for ra, rb in zip(a, b):
        assert ra.accuracy == rb.accuracy
        assert ra.history == rb.history


# ---------------------------------------------------------------------------
# jit-safe time model
# ---------------------------------------------------------------------------


def _time_args(fl, n=10, sel=4, failures=0):
    from repro.core import selection as sel_lib

    util = sel_lib.init_utility_state(n, key=jax.random.key(0))
    mask = jnp.zeros((n,)).at[:sel].set(1.0)
    failed = jnp.zeros((n,)).at[:failures].set(1.0)
    return util, mask, failed


def test_simulate_round_time_is_jit_invariant(fl):
    util, mask, failed = _time_args(fl, failures=2)
    eager = fl_driver.simulate_round_time(fl, util, mask, failed)
    jitted = jax.jit(
        lambda u, m, f: fl_driver.simulate_round_time(fl, u, m, f)
    )(util, mask, failed)
    np.testing.assert_allclose(float(eager), float(jitted), rtol=1e-6)


def test_simulate_round_time_ordering(fl):
    util, mask, _ = _time_args(fl)
    zero = jnp.zeros_like(mask)
    t_clean = float(fl_driver.simulate_round_time(fl, util, mask, zero))
    # failures cost time, under fault tolerance and (more) without it
    _, _, failed = _time_args(fl, failures=3)
    t_fail_ft = float(fl_driver.simulate_round_time(fl, util, mask, failed))
    no_ft = dataclasses.replace(fl, fault_tolerance=False)
    t_clean_noft = float(fl_driver.simulate_round_time(no_ft, util, mask, zero))
    t_fail_noft = float(fl_driver.simulate_round_time(no_ft, util, mask, failed))
    assert t_fail_ft > t_clean
    assert t_fail_noft > t_clean_noft
    # empty selection degenerates to pure communication time
    t_empty = float(fl_driver.simulate_round_time(fl, util, zero, zero))
    assert t_empty == pytest.approx(0.35)


# ---------------------------------------------------------------------------
# device-side batch sampling
# ---------------------------------------------------------------------------


def test_sample_round_batches_respects_client_sizes(fed):
    stack = stack_federation(fed)
    b = jax.jit(
        lambda k: sample_round_batches(k, stack, local_steps=4, batch=8)
    )(jax.random.key(0))
    assert b["x"].shape == (fed.n_clients, 4, 8, fed.n_features)
    assert b["y"].shape == (fed.n_clients, 4, 8)
    # every sampled row must exist in that client's shard (never padding):
    # rows are drawn from [0, size_i), so labels match the client's own data
    for ci in (0, fed.n_clients - 1):
        rows = np.asarray(b["x"][ci]).reshape(-1, fed.n_features)
        src = fed.x[ci]
        assert len(src) == int(stack.sizes[ci])
        for r in rows[:8]:
            assert np.isclose(src, r, atol=1e-6).all(axis=1).any()


@pytest.mark.parametrize("dataset,n_samples,width",
                         [("unsw", 2_000, 128), ("road_raw", 600, 512)])
def test_sample_round_batches_equal_plain_per_client_draws(dataset,
                                                           n_samples, width):
    """The row-table sampler returns, to the bit, what each client's own
    shard gives at the same indices: the key split into one key per client,
    ``randint(k, (steps, batch), 0, size)``, then ``fed.x[c][idx]`` and
    ``fed.y[c][idx]``.  So no pad row (``i >= size``) and no pad lane (the
    label's, or the zeros after it) reaches a batch."""
    fed = make_federated(3, dataset, n_samples=n_samples, n_clients=6)
    stack = stack_federation(fed)
    assert stack.rows.shape[1] == width
    steps, batch = 3, 16
    key = jax.random.key(11)
    b = jax.jit(lambda k: sample_round_batches(k, stack, steps, batch))(key)
    assert b["x"].shape == (fed.n_clients, steps, batch, fed.n_features)
    assert b["x"].dtype == jnp.float32 and b["y"].dtype == jnp.int32
    for ci, k in enumerate(jax.random.split(key, fed.n_clients)):
        idx = np.asarray(jax.random.randint(k, (steps, batch), 0,
                                            len(fed.x[ci])))
        np.testing.assert_array_equal(
            np.asarray(b["x"][ci]).view(np.uint32),
            fed.x[ci][idx].astype(np.float32).view(np.uint32))
        np.testing.assert_array_equal(np.asarray(b["y"][ci]),
                                      fed.y[ci][idx].astype(np.int32))


# ---------------------------------------------------------------------------
# DP routing equivalence (kernel vs fallback)
# ---------------------------------------------------------------------------


def test_privatize_update_routing_is_neutral():
    """use_kernel=True (ref fallback on CPU / Pallas on TPU) and the plain
    jnp path must produce the same noised update — routing never changes
    the mechanism."""
    key = jax.random.key(7)
    tree = {"w": jax.random.normal(key, (65, 33)) * 3.0,
            "b": jax.random.normal(jax.random.fold_in(key, 1), (129,))}
    a, na = dp_lib.privatize_update(tree, key, mode="clipped", clip=0.7,
                                    sigma=0.2, use_kernel=True)
    b, nb = dp_lib.privatize_update(tree, key, mode="clipped", clip=0.7,
                                    sigma=0.2, use_kernel=False)
    np.testing.assert_allclose(float(na), float(nb), rtol=1e-6)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
