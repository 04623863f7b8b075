"""Unified observability layer (ISSUE 8, docs/DESIGN.md §8).

Three coupled pieces, all HOST-side — nothing here enters a traced
function, so every training/eval lane is bitwise identical with telemetry
on or off (tests/test_obs.py pins it):

* ``obs/trace.py`` — nested host spans (epoch-ns and wall clocks),
  compile-event capture keyed on the runner/scorer caches, structured JSONL
  emission, and
  the ``jax.profiler`` integration (``profile_trace`` dumps a
  TensorBoard-loadable trace; spans double as
  ``jax.profiler.TraceAnnotation`` phase markers while profiling).
* ``obs/stats.py`` — the :class:`StatsRegistry`: one snapshot/reset/assert
  API over every ad-hoc counter dict the repo grew
  (``fl_driver.RUNNER_STATS``, ``serve.engine.SERVE_STATS`` are registry
  views now — their dict-style call sites work unchanged).
* ``obs/store.py`` — the embedded indexed experiment store (single-file
  SQLite, append-only runs/cells/metrics) every bench writes through;
  ``tools/bench_regress.py`` queries its history for CI regression gates.
"""
from repro.obs.stats import STATS, Counters, StatsRegistry
from repro.obs.trace import (TRACER, Tracer, event, profile_trace, span,
                             spans)
from repro.obs.store import ExperimentStore, default_store, default_store_path

__all__ = [
    "STATS", "Counters", "StatsRegistry",
    "TRACER", "Tracer", "event", "profile_trace", "span", "spans",
    "ExperimentStore", "default_store", "default_store_path",
]
