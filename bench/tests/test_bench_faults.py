"""A run drives the whole harness on the CPU (no look for a chip) at a
small size; with the timed path broken underneath, ``correct`` comes out
false, once for each fault the cell can have."""
import time

import pytest

from bench import harness
from bench.tests.small import (clear_program_caches, shrink_population,
                               shrink_serve, shrink_sweep)


def _state_unchanged(monkeypatch):
    from repro.core import aggregation
    monkeypatch.setattr(aggregation, "apply_server_update",
                        lambda server, params, state, delta: (params, state))


def _half_batch(monkeypatch):
    from repro.train import fl_driver
    full = fl_driver.sample_round_batches

    def half(key, stack, steps, batch):
        b = full(key, stack, steps, batch)
        return {k: v[:, :, : batch // 2] for k, v in b.items()}

    monkeypatch.setattr(fl_driver, "sample_round_batches", half)


def _half_cohort_batch(monkeypatch):
    from repro.train import fl_driver
    full = fl_driver.sample_cohort_batches

    def half(key, pop, idx, steps, batch):
        b = full(key, pop, idx, steps, batch)
        return {k: v[:, :, : batch // 2] for k, v in b.items()}

    monkeypatch.setattr(fl_driver, "sample_cohort_batches", half)


def _answer_altered(monkeypatch):
    from repro.serve import engine
    get = engine._get_scorer

    def altered(*a, **k):
        scorer = get(*a, **k)
        return lambda params, x: scorer(params, x).at[0].add(1e-3)

    monkeypatch.setattr(engine, "_get_scorer", altered)


@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_sweep_check(fault, bench_env, monkeypatch):
    clear_program_caches()
    if fault is not None:
        fault(monkeypatch)
    out = harness.run("mlp_unsw.sweep_dp", 2**31 + 5, 1.0, False,
                      t_start=time.time(), require_chip=False,
                      cell_hook=shrink_sweep, root=bench_env)
    clear_program_caches()
    assert out["correct"] is (fault is None), out["check"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["metrics"]["lane_rounds_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_cohort_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_population_check(fault, bench_env, monkeypatch):
    clear_program_caches()
    if fault is not None:
        fault(monkeypatch)
    out = harness.run("mlp_unsw.population_100k", 2**32 + 7, 1.0, False,
                      t_start=time.time(), require_chip=False,
                      cell_hook=shrink_population, root=bench_env)
    clear_program_caches()
    assert out["correct"] is (fault is None), out["check"]
    assert out["attempted"] >= 2 and out["failed"] == 0


@pytest.mark.parametrize("fault", [None, _answer_altered],
                         ids=["sound", "answer_altered"])
def test_serve_check(fault, bench_env, monkeypatch):
    clear_program_caches()
    if fault is not None:
        fault(monkeypatch)
    out = harness.run("ssm_road.serve_open", 3, 1.0, False,
                      t_start=time.time(), require_chip=False,
                      cell_hook=shrink_serve, root=bench_env)
    clear_program_caches()
    assert out["correct"] is (fault is None), out["check"]
    assert out["attempted"] == 2000 and out["failed"] == 0


def test_compile_in_the_window_counts_as_failed(bench_env):
    """The guard trips when the window meets a shape set-up did not warm."""
    clear_program_caches()

    def new_bucket(drv):
        window = drv.window

        def shifted(seconds, prof=None):
            drv.engine.buckets = (16, 64)
            return window(seconds, prof)

        drv.window = shifted

    out = harness.run("ssm_road.serve_open", 4, 1.0, False,
                      t_start=time.time(), require_chip=False,
                      cell_hook=shrink_serve, driver_hook=new_bucket,
                      root=bench_env)
    clear_program_caches()
    assert out["compiles_in_window"] >= 1
    assert out["failed"] >= 1
