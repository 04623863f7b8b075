"""Shared fixtures of the benchmark's own tests (CPU, small sizes)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def bench_env(tmp_path, monkeypatch):
    """A checkout root for the harness in a temporary directory: the
    benchmark's files, and a ``BENCHMARK.json`` that adds the serving cell
    kept in ``data/serve_cell.json`` (built and tested, but not among the
    measured cells).  JAX's compile cache and libtpu's logs go there too;
    the JAX settings are restored after."""
    import json
    import jax
    from bench import harness
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_max_size")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path / "tpu_logs"))

    real = harness.setup_environment
    monkeypatch.setattr(harness, "setup_environment",
                        lambda root=None: real(str(tmp_path)))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "serve_cell.json")) as f:
        for key, entries in json.load(f).items():
            spec[key] += entries
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    os.symlink(os.path.join(ROOT, "bench"), tmp_path / "bench")
    yield str(tmp_path)
    for k, v in saved.items():
        jax.config.update(k, v)
