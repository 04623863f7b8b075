"""Observability layer (ISSUE 8): tracer, stats registry, experiment
store, regression gate, schema checker.

The hard property is **bitwise neutrality**: turning the tracer on must
not change a single bit of ``run_fl_batch``'s outputs — spans time host
phases only, and the device-side markers are ``jax.named_scope`` metadata.
The rest is the store/gate machinery ``benchmarks/common.record_bench``
and ``tools/bench_regress.py`` are built on.
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.data.synthetic import make_federated, make_population
from repro.obs import TRACER, profile_trace  # noqa: F401 — re-export check
from repro.obs.stats import StatsRegistry
from repro.obs.store import ExperimentStore
from repro.obs.trace import Tracer
from repro.train import fl_driver

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))          # tools/ + benchmarks/ imports
sys.path.insert(0, str(ROOT / "tools"))

import bench_regress  # noqa: E402
import check_bench_schema  # noqa: E402


# ---------------------------------------------------------------------------
# tracer: nesting, timing, events, zero-cost-off
# ---------------------------------------------------------------------------

def test_spans_nest_with_depth_and_parent():
    tr = Tracer()
    tr.enable()
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            tr.event("tick", n=7)
    outer = tr.find("outer")[0]
    inners = tr.find("inner")
    assert len(inners) == 2
    assert outer.depth == 0 and outer.parent == -1
    assert all(s.depth == 1 and s.parent == outer.index for s in inners)
    assert outer.wall_s >= max(s.wall_s for s in inners) >= 0.0
    assert outer.attrs == {"k": 1}
    (ev,) = tr.events
    assert ev["name"] == "tick" and ev["n"] == 7 and ev["depth"] == 2


def test_disabled_tracer_records_nothing_and_returns_shared_noop():
    tr = Tracer()
    cm1, cm2 = tr.span("a"), tr.span("b")
    assert cm1 is cm2                      # shared null object, no alloc
    with tr.span("a"):
        tr.event("e")
    assert tr.spans == [] and tr.events == []


def test_jsonl_dump_round_trips(tmp_path):
    tr = Tracer()
    tr.enable()
    with tr.span("phase", rep=0):
        tr.event("compile", engine="sweep")
    path = tr.dump_jsonl(str(tmp_path / "trace.jsonl"))
    rows = [json.loads(ln) for ln in Path(path).read_text().splitlines()]
    kinds = {r["type"] for r in rows}
    assert kinds == {"span", "event"} and len(rows) == 2
    sp = next(r for r in rows if r["type"] == "span")
    assert sp["name"] == "phase" and sp["rep"] == 0 and sp["wall_s"] >= 0
    # one realtime clock in epoch ns, readable as epoch seconds too
    assert sp["t0_ns"] <= sp["t1_ns"] and "cpu_s" not in sp
    assert sp["t0"] == pytest.approx(sp["t0_ns"] * 1e-9)
    ev = next(r for r in rows if r["type"] == "event")
    assert sp["t0_ns"] <= ev["t0_ns"] == ev["t1_ns"] <= sp["t1_ns"]


# ---------------------------------------------------------------------------
# stats registry: dict-compat views, delta/expect/reset
# ---------------------------------------------------------------------------

def test_counters_behave_like_the_dicts_they_replaced():
    reg = StatsRegistry()
    stats = reg.counters("runner", misses=0, hits=0)
    m0 = stats["misses"]
    stats["misses"] += 1
    stats["hits"] += 3
    assert stats["misses"] - m0 == 1
    assert dict(stats) == {"misses": 1, "hits": 3}
    assert reg.counters("runner") is stats     # module aliases stay views
    reg.reset("runner")
    assert dict(stats) == {"misses": 0, "hits": 0}


def test_registry_delta_and_expect():
    reg = StatsRegistry()
    st = reg.counters("ns", a=0, b=0)
    with reg.delta("ns") as d:
        st["a"] += 2
    assert d == {"a": 2, "b": 0}
    with reg.expect("ns", a=1):
        st["a"] += 1
    with pytest.raises(AssertionError):
        with reg.expect("ns", a=1):
            pass                                # no move -> delta 0 != 1


def test_live_registries_are_registered_namespaces():
    from repro.obs.stats import STATS
    from repro.serve import engine as serve_engine

    snap = STATS.snapshot()
    assert "runner" in snap and "serve" in snap
    assert dict(fl_driver.RUNNER_STATS) == snap["runner"]
    assert dict(serve_engine.SERVE_STATS) == snap["serve"]


# ---------------------------------------------------------------------------
# bitwise neutrality: tracer on == tracer off
# ---------------------------------------------------------------------------

def test_telemetry_is_bitwise_neutral():
    fed = make_federated(0, "unsw", n_samples=600, n_clients=6)
    fl = FLConfig(n_clients=6, clients_per_round=3, rounds=4, local_epochs=2,
                  local_batch=32, local_lr=0.1, dp_enabled=True,
                  dp_mode="clipped", dp_epsilon=1000.0, dp_clip=1.0,
                  fault_tolerance=True, failure_prob=0.1)

    def go():
        fl_driver._RUNNER_CACHE.clear()
        res = fl_driver.run_fl_batch(fed, fl, "proposed", seeds=(0, 1),
                                     rounds=4, eval_every=2)
        return [(r.accuracy, r.auc, r.eps_spent,
                 tuple(np.asarray(r.history["acc"]).tolist())) for r in res]

    on, off = _on_and_off(go, lambda: _front_door_spans(
        "sweep", ("prepare", "execute", "readback", "results"), 2))
    assert on == off, "telemetry changed the engine's outputs"


def _on_and_off(go, check=None):
    """``go()`` with the global tracer off, then on; ``check`` reads the
    spans and events of the traced call."""
    was = TRACER.enabled
    TRACER.disable()
    off = go()
    TRACER.enable()
    try:
        on = go()
        assert TRACER.find("runner.build"), "instrumented build span missing"
        assert any(e["name"] == "compile.runner_miss" for e in TRACER.events)
        if check is not None:
            check()
    finally:
        TRACER.disable()
        TRACER.clear()
        if was:
            TRACER.enable()
    return on, off


def _front_door_spans(door: str, phases, n_lanes: int):
    """Each phase span of ``door`` recorded once, with its attributes."""
    for phase in phases:
        (sp,) = TRACER.find(f"{door}.{phase}")
        assert sp.attrs["n_lanes"] == n_lanes and sp.t0_ns <= sp.t1_ns
    (prep,) = TRACER.find(f"{door}.prepare")
    assert prep.attrs["method"] == "proposed" and prep.attrs["rounds"] == 4


def test_population_telemetry_is_bitwise_neutral():
    pop = make_population(0, n_clients=64, pool_samples=600,
                          members_per_client=8)
    fl = FLConfig(n_clients=64, clients_per_round=4, k_max=4, rounds=4,
                  local_epochs=2, local_batch=32, local_lr=0.1,
                  dp_enabled=True, dp_mode="clipped", dp_epsilon=1000.0,
                  dp_clip=1.0, fault_tolerance=True, failure_prob=0.1)

    def go():
        fl_driver._RUNNER_CACHE.clear()
        res = fl_driver.run_fl_population(pop, fl, seeds=(0, 1), rounds=4,
                                          eval_every=2)
        return [(r.accuracy, r.auc, r.eps_spent, r.sim_time_s,
                 tuple(np.asarray(r.history["loss"]).tolist()))
                for row in res for r in row]

    on, off = _on_and_off(go, lambda: _front_door_spans(
        "population", ("prepare", "execute", "readback", "results"), 2))
    assert on == off, "telemetry changed the population engine's outputs"


# ---------------------------------------------------------------------------
# experiment store: round-trip + indexed queries
# ---------------------------------------------------------------------------

def _tiny_store(tmp_path, n_runs=1, wall=1.0):
    store = ExperimentStore(str(tmp_path / "exp.sqlite"))
    for i in range(n_runs):
        rid = store.begin_run(engine_rev="models4", backend="cpu",
                              mode="test", sha=f"sha{i}")
        store.record_cell(
            rid, "engine", "batch_warm", statics_key="abc123",
            wall_cold_s=9.0, warm_walls=[wall + 0.01 * i, wall + 0.02],
            lane_params={"rounds": 4},
            metrics={"auc_mean": (0.9, 1), "ratio": (1.1, -1),
                     "info": 42.0})
    return store


def test_store_round_trip_and_history(tmp_path):
    store = _tiny_store(tmp_path, n_runs=3)
    assert store.run_ids() == [1, 2, 3]
    assert store.latest_run_id() == 3
    (cell,) = store.cells_of_run(3)
    assert cell["bench"] == "engine" and cell["lane_key"] == "batch_warm"
    assert cell["engine_rev"] == "models4" and cell["git_sha"] == "sha2"
    assert cell["wall_warm_s"] == min(cell["warm_walls"])
    assert cell["lane_params"] == {"rounds": 4}
    assert cell["metrics"]["auc_mean"] == {"value": 0.9, "direction": 1}
    assert cell["metrics"]["info"]["direction"] == 0

    hist = store.history("engine", "batch_warm", engine_rev="models4",
                         statics_key="abc123", before_run=3)
    assert [c["run_id"] for c in hist] == [1, 2]
    assert store.history("engine", "batch_warm",
                         statics_key="other") == []
    traj = store.metric_history("engine", "batch_warm", "auc_mean")
    assert traj == [(1, 0.9), (2, 0.9), (3, 0.9)]
    assert store.lanes("engine") == [("engine", "batch_warm")]
    assert store.query_plan_uses_index()
    store.close()


def test_metric_trajectory_crosses_engine_revs(tmp_path):
    # metric_history is per-rev by design; the trajectory report is the
    # complementary cross-rev view, each point labelled with its rev.
    store = ExperimentStore(str(tmp_path / "exp.sqlite"))
    for rev, auc in [("models3", 0.58), ("models4", 0.61),
                     ("models4", 0.63)]:
        rid = store.begin_run(engine_rev=rev, backend="cpu", mode="test")
        store.record_cell(rid, "hillclimb", "A:baseline",
                          metrics={"roofline_s": (auc, -1)})
    traj = store.metric_trajectory("hillclimb", "A:baseline", "roofline_s")
    assert traj == [(1, "models3", 0.58), (2, "models4", 0.61),
                    (3, "models4", 0.63)]
    report = store.trajectory_report("hillclimb", "roofline_s")
    assert "A:baseline" in report and "models3" in report
    assert "no stored cells" in store.trajectory_report("hillclimb",
                                                        "nope")
    assert store.metric_trajectory("hillclimb", "A:baseline", "nope") == []
    store.close()


# ---------------------------------------------------------------------------
# regression gate: idle without history, fires on injection, quiet on replay
# ---------------------------------------------------------------------------

BASE_WALLS = [1.00, 1.03, 0.98]          # jittered — ties break Mann-Whitney


def _cell(walls, auc=0.90, run_id=9):
    return {"bench": "engine", "lane_key": "batch_warm", "run_id": run_id,
            "warm_walls": list(walls),
            "metrics": {"auc_mean": {"value": auc, "direction": 1}}}


def _history(n_runs=3):
    return [_cell([w + 0.005 * i for w in BASE_WALLS], run_id=i + 1)
            for i in range(n_runs)]


def test_gate_idle_on_insufficient_history():
    _, regressions = bench_regress.check_cell(
        _cell([3.0, 3.1, 3.2]), _history(1))
    assert regressions == []              # 1 run < min_history_runs=2


def test_gate_fires_on_injected_wall_regression():
    _, regressions = bench_regress.check_cell(
        _cell([3.0, 3.1, 2.9]), _history(3))
    assert len(regressions) == 1 and "warm wall" in regressions[0]


def test_gate_quiet_on_replay():
    _, regressions = bench_regress.check_cell(
        _cell([1.01, 0.99, 1.02]), _history(3))
    assert regressions == []


def test_gate_needs_both_significance_and_ratio():
    # consistently 1% slower: MW may flag it, but the 1.25x ratio guard
    # keeps one-percent drift out of the failure set
    _, regressions = bench_regress.check_cell(
        _cell([w * 1.01 for w in BASE_WALLS]), _history(3))
    assert regressions == []


def test_gate_fires_on_gated_metric_drop():
    _, regressions = bench_regress.check_cell(
        _cell([1.0, 1.01, 0.99], auc=0.70), _history(3))
    assert len(regressions) == 1 and "auc_mean" in regressions[0]


def test_check_store_end_to_end(tmp_path):
    store = _tiny_store(tmp_path, n_runs=3)
    # replay run: same walls -> quiet
    _, regressions = bench_regress.check_store(store)
    assert regressions == []
    # injected run: 3x walls + auc collapse -> both gates fire
    rid = store.begin_run(engine_rev="models4", mode="test", sha="bad")
    store.record_cell(rid, "engine", "batch_warm", statics_key="abc123",
                      warm_walls=[3.0, 3.05, 2.95],
                      metrics={"auc_mean": (0.5, 1), "ratio": (1.1, -1)})
    verdicts, regressions = bench_regress.check_store(store)
    assert len(regressions) == 2
    assert any("warm wall" in r for r in regressions)
    assert any("auc_mean" in r for r in regressions)
    store.close()


# ---------------------------------------------------------------------------
# orchestrator gate map + schema checker against the repo's real artifacts
# ---------------------------------------------------------------------------

def test_run_py_gate_checks():
    from benchmarks import run as run_mod

    assert run_mod.check_gates(
        "engine", {"acceptance": {"pass_under_2x": False}})
    assert not run_mod.check_gates(        # un-gated smoke verdict
        "sweep", {"acceptance": {"pass_warm_not_slower": False,
                                 "gated": False}})
    assert not run_mod.check_gates("engine", None)
    names = run_mod.discover()
    assert {"engine", "sweep", "privacy", "fault", "models", "serve",
            "scale"} <= set(names)


def test_bench_schema_checker_on_real_artifacts():
    present = [b for b in check_bench_schema.SCHEMAS
               if (ROOT / b).exists()]
    if not present:
        pytest.skip("no BENCH_*.json artifacts in this checkout")
    r = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "check_bench_schema.py"),
         "--root", str(ROOT)],
        capture_output=True, text=True)
    assert r.returncode == 0, f"\n{r.stdout}{r.stderr}"


def test_bench_schema_checker_flags_corruption(tmp_path):
    bad = {"mode": "full"}                 # everything else missing
    p = tmp_path / "BENCH_engine.json"
    p.write_text(json.dumps(bad))
    errs = check_bench_schema.check_file(
        str(p), check_bench_schema.SCHEMAS["BENCH_engine.json"])
    assert errs
