"""The Pallas kernels of the main path compile for a TPU v5e.

Interpret mode accepts block shapes the TPU compiler refuses, so every
kernel is also compiled here, ahead of time, for one chip of a *described*
``v5e:2x2`` topology (no chip needs to be attached), in the form the main
path calls it:

* the DP clip+noise tree (``sumsq`` + ``scale_noise``) vmapped over the
  engine's 40 clients at the ``mlp`` leaf shapes, with the traced clip and
  sigma of the runtime lanes;
* each sequence detector's kernel-route scorer at the serving buckets 16
  and 128 (``ssm``/``rglru`` → ``rglru_scan``; ``attn`` →
  ``flash_attention`` + ``flash_decode``), and its eval form vmapped over
  sweep lanes, as the training runner evaluates it;
* the dense plan's batch sampler at the ``mlp_unsw.sweep_dp`` cell's
  shapes, vmapped over its 8 lanes: each draw must be one whole row of the
  row-major client table, with no relayout of the table;
* the sharded runners on all four chips: a 4-lane ``run_fl_sweep`` runner
  on its ``lane`` mesh and a ``run_fl_population`` runner on a (2, 2)
  lane × client mesh, whose kernels must see one lane's operands per
  device (not every lane's, gathered).

Each compiled program must contain the Mosaic kernels (``tpu_custom_call``).
The topology is described only inside a fixture, so collecting this file
never loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.configs.base import FLConfig
from repro.data.synthetic import (StackedFederation, make_federated,
                                  make_population, sample_round_batches)
from repro.kernels import backend
from repro.kernels import ops
from repro.models import sharding as shard_lib
from repro.models.spec import DataMeta, get_model_spec, meta_for
from repro.train import fl_driver

N_CLIENTS = 40
UNSW = DataMeta(n_features=42, n_classes=2, hidden=64, feature_shape=(42,))
ROAD_RAW = DataMeta(n_features=384, n_classes=2, hidden=64,
                    feature_shape=(64, 6))
# kernels per program, counted as tpu_custom_call targets in the HLO
SEQ_KERNELS = {"ssm": 1, "rglru": 1, "attn": 2}
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but can
    never be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch, no_compile_cache):
    """The kernels and the DP route follow the process's backend, which is
    the CPU here; route them as they run on the chip instead.  Runners built
    meanwhile are dropped from the engine's cache afterwards."""
    monkeypatch.setattr(backend, "pallas_backend_ready", lambda: True)
    before = set(fl_driver._RUNNER_CACHE)
    yield
    for key in set(fl_driver._RUNNER_CACHE) - before:
        del fl_driver._RUNNER_CACHE[key]


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _kernel_count(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(CUSTOM_CALL)


def test_dp_clip_noise_tree_vmapped_over_clients(one_chip, compiled_kernels):
    spec = get_model_spec("mlp", UNSW)
    leaf = jax.eval_shape(spec.init, jax.random.key(0))
    deltas = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((N_CLIENTS,) + s.shape, s.dtype), leaf)
    raw_keys = jax.ShapeDtypeStruct((N_CLIENTS, 2), jnp.uint32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)

    def privatize(d, raw, clip, sigma):
        keys = jax.random.wrap_key_data(raw)
        return jax.vmap(lambda t, k: ops.dp_clip_noise_tree(
            t, k, clip, sigma, interpret=False))(d, keys)

    n_leaves = len(jax.tree.leaves(leaf))
    count = _kernel_count(privatize, *_on(one_chip, (deltas, raw_keys,
                                                     scalar, scalar)))
    assert count >= 2 * n_leaves    # one sumsq + one scale_noise per leaf


def test_batch_sampler_reads_whole_rows(one_chip, no_compile_cache):
    """The ``mlp_unsw.sweep_dp`` cell's table (40 clients × 29,877 padded
    rows of 42 features + label, 128 lanes) sampled by 8 lanes.  One local
    step of 64 draws per client: the layout does not depend on the number
    of draws, and the compile's time grows with it (about tenfold at the
    cell's 5 steps)."""
    n, max_n, d, n_test, lanes = 40, 29_877, 42, 64_418, 8
    table = (n * max_n, 128)
    stack = StackedFederation(
        rows=jax.ShapeDtypeStruct(table, jnp.float32),
        sizes=jax.ShapeDtypeStruct((n,), jnp.int32),
        test_x=jax.ShapeDtypeStruct((n_test, d), jnp.float32),
        test_y=jax.ShapeDtypeStruct((n_test,), jnp.int32))
    keys = jax.ShapeDtypeStruct((lanes,), jax.random.key(0).dtype)
    sample = jax.vmap(lambda k, st: sample_round_batches(k, st, 1, 64),
                      in_axes=(0, None))
    hlo = jax.jit(sample).lower(*_on(one_chip, (keys, stack))).compile(
        ).as_text()
    slices = [tuple(int(v) for v in m.split(","))
              for m in re.findall(r" gather\(.*slice_sizes=\{([\d,]*)\}", hlo)]
    assert slices and all(sl[-1] % 128 == 0 for sl in slices), slices
    shape = r"f32\[%d,%d\]" % table
    layouts = re.findall(shape + r"\{([\d,]+)", hlo)
    assert layouts and set(layouts) == {"1,0"}, layouts
    table_dims = r"[\d,]*\b(?:%d|%d)\b[\d,]*" % (table[0], max_n)
    assert not re.search(r"= \w+\[%s\]\S* copy\(" % table_dims, hlo)


def _kernel_calls(hlo: str):
    """(instruction name, op_name) of every Mosaic kernel call."""
    out = []
    for line in hlo.splitlines():
        if CUSTOM_CALL in line:
            name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line).group(1)
            op_name = re.search(r'op_name="([^"]*)"', line)
            out.append((name, op_name.group(1) if op_name else ""))
    return out


def test_kernels_keep_their_names_and_scopes(one_chip, compiled_kernels):
    """The custom call of a Pallas kernel is named after its ``name=``
    (``dp_sumsq.N``, ``dp_scale_noise.N``, ``rglru_scan.N``), and its
    ``op_name`` holds the scope a trace reader looks for."""
    spec = get_model_spec("mlp", UNSW)
    leaf = jax.eval_shape(spec.init, jax.random.key(0))
    deltas = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((N_CLIENTS,) + s.shape, s.dtype), leaf)
    raw_keys = jax.ShapeDtypeStruct((N_CLIENTS, 2), jnp.uint32)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)

    def privatize(d, raw, clip, sigma):
        keys = jax.random.wrap_key_data(raw)
        return jax.vmap(lambda t, k: ops.dp_clip_noise_tree(
            t, k, clip, sigma, interpret=False))(d, keys)

    hlo = jax.jit(privatize).lower(*_on(one_chip, (deltas, raw_keys, scalar,
                                                   scalar))).compile().as_text()
    calls = _kernel_calls(hlo)
    assert {n.split(".")[0] for n, _ in calls} == {"dp_sumsq", "dp_scale_noise"}
    assert all("/dp_kernel/" in op for _, op in calls), calls

    seq = jax.ShapeDtypeStruct((8, 128, 512), jnp.float32)
    hlo = jax.jit(lambda a, x: ops.rglru_scan(a, x, interpret=False)).lower(
        *_on(one_chip, (seq, seq))).compile().as_text()
    calls = _kernel_calls(hlo)
    assert calls and all(n.startswith("rglru_scan") and "rglru_scan/" in op
                         for n, op in calls), calls


@pytest.mark.parametrize("bucket", [16, 128])
@pytest.mark.parametrize("model", sorted(SEQ_KERNELS))
def test_kernel_route_scorer(one_chip, compiled_kernels, model, bucket):
    spec = get_model_spec(model, ROAD_RAW)
    params = jax.eval_shape(spec.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((bucket, ROAD_RAW.n_features), jnp.float32)
    count = _kernel_count(spec.logits_routed("kernel"),
                          *_on(one_chip, (params, x)))
    assert count >= SEQ_KERNELS[model]


@pytest.mark.parametrize("model", sorted(SEQ_KERNELS))
def test_kernel_route_eval_vmapped_over_lanes(one_chip, compiled_kernels,
                                              model):
    spec = get_model_spec(model, ROAD_RAW)
    one = jax.eval_shape(spec.init, jax.random.key(0))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((2,) + s.shape, s.dtype), one)
    test_x = jax.ShapeDtypeStruct((225, ROAD_RAW.n_features), jnp.float32)
    logits = jax.vmap(spec.logits_routed("kernel"), in_axes=(0, None))
    count = _kernel_count(logits, *_on(one_chip, (params, test_x)))
    assert count >= SEQ_KERNELS[model]


COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "reduce-scatter")


def _kernel_operand_shapes(hlo: str):
    """Operand shapes of every Mosaic kernel call in compiled HLO text."""
    shapes = []
    for line in hlo.splitlines():
        if CUSTOM_CALL in line:
            m = re.search(r"operand_layout_constraints=\{(.*?)\}, ", line)
            shapes += [tuple(int(d) for d in dims.split(",") if d)
                       for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]
    return shapes


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def test_sweep_lanes_stay_on_their_device(topo, compiled_kernels):
    """4 lanes on 4 chips: each chip runs one lane whole.  The DP kernel's
    operands hold that lane's 40 clients, and the program has no
    collective at all."""
    mesh = Mesh(np.asarray(topo.devices), ("lane",))
    fed = make_federated(0, "unsw", n_clients=N_CLIENTS)
    fl = FLConfig(rounds=2)
    stack, size, quality = fl_driver._device_federation(fed)
    runner = fl_driver._get_runner(fl, 2, 2, meta_for(fed, hidden=64), 4,
                                   stack, sharded=True)
    lane = NamedSharding(mesh, PartitionSpec("lane"))
    rep = NamedSharding(mesh, PartitionSpec())
    keys = jax.ShapeDtypeStruct((4,), jax.random.key(0).dtype, sharding=lane)
    lanes = jax.tree.map(lambda x: _sds(x, lane),
                         fl_driver._params_lanes([fl], 4))
    data = jax.tree.map(lambda x: _sds(x, rep), (stack, size, quality))
    with jax.set_mesh(mesh):
        hlo = runner.lower(keys, *data, lanes).compile().as_text()
    shapes = _kernel_operand_shapes(hlo)
    assert shapes and all(s[0] == N_CLIENTS for s in shapes), shapes
    assert not [op for op in COLLECTIVES if op in hlo]


def test_population_lanes_bind_to_the_lane_axis(topo, compiled_kernels):
    """2 lanes on a (2, 2) lane × client mesh: each chip's DP kernel sees
    its own lane's cohort of ``k_max`` clients, and no lane is gathered."""
    k_max = 16
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("lane", "client"),
                axis_types=(AxisType.Auto,) * 2)
    pop = make_population(0, n_clients=4096, pool_samples=2000,
                          members_per_client=32)
    fl = FLConfig(n_clients=4096, clients_per_round=k_max, k_max=k_max,
                  rounds=2, local_epochs=2, local_batch=32)
    runner = fl_driver._get_population_runner(
        fl, 2, 2, meta_for(pop, hidden=64), 2, pop, 1, sharded=True)
    s_lane, _ = shard_lib.lane_shardings(mesh)
    keys = jax.ShapeDtypeStruct((2,), jax.random.key(0).dtype,
                                sharding=s_lane)
    lanes = jax.tree.map(lambda x: _sds(x, s_lane),
                         fl_driver._params_lanes([fl], 2))
    pop_in = jax.tree.map(_sds, pop, shard_lib.population_shardings(mesh, pop))
    with jax.set_mesh(mesh):
        hlo = runner.lower(keys, pop_in, lanes).compile().as_text()
    shapes = _kernel_operand_shapes(hlo)
    assert shapes and all(s[0] == k_max for s in shapes), shapes
    assert "all-gather" not in hlo
