"""Every op of an FL round runs under a named phase scope.

A device trace attributes time by the ``op_name`` metadata each HLO
instruction carries (``bench/trace/xplane.py``).  An op of the round loop
outside the phase scopes is device time that no per-layer metric can
follow, so both round runners (the dense sweep's and the population's) are
compiled here at tiny shapes and every instruction of the checked kinds in
the round body must name one phase.  The DP clip+noise tree must split
into its layout, noise and kernel scopes.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import FLConfig
from repro.data.synthetic import make_federated, make_population
from repro.kernels import ops
from repro.models.spec import meta_for
from repro.train import fl_driver

# core/rounds.py (the round step) and train/fl_driver.py (the loop around it)
PHASES = {"selection", "fault", "batch_sample", "local_train",
          "dp_privatize", "aggregate", "carry_update", "bookkeeping"}
CHECKED = {"dot", "gather", "scatter", "reduce", "sort", "rng",
           "rng-bit-generator", "custom-call"}
# the round scan inside the eval-block scan, as the compiled HLO names it
ROUND_BODY = "while/body/closed_call/while/body/closed_call/"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?\S+\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _round_ops(hlo: str):
    """(opcode, op_name) of every instruction in the round body."""
    out = []
    for line in hlo.splitlines():
        m, name = _INSTR.match(line), _OP_NAME.search(line)
        if m and name and ROUND_BODY in name.group(1):
            out.append((m.group(1), name.group(1)))
    return out


def _assert_every_op_in_a_phase(hlo: str):
    ops_ = _round_ops(hlo)
    checked = [(opc, name) for opc, name in ops_ if opc in CHECKED]
    assert len(checked) > 20, "the round body was not found in the HLO"
    outside = [(opc, name) for opc, name in checked
               if not PHASES & set(name.split("/"))]
    assert not outside, outside[:10]
    seen = {p for _, name in ops_ for p in name.split("/")} & PHASES
    assert seen == PHASES, sorted(PHASES - seen)


FL = dict(local_epochs=2, local_batch=32, local_lr=0.1, dp_enabled=True,
          dp_mode="clipped", dp_epsilon=8.0, dp_clip=1.0,
          fault_tolerance=True, failure_prob=0.1)


def test_dense_round_body_is_scoped():
    fed = make_federated(0, "unsw", n_samples=600, n_clients=6)
    fl = fl_driver.fl_for_method(
        FLConfig(n_clients=6, clients_per_round=3, rounds=4, **FL),
        "proposed")
    stack, size, quality = fl_driver._device_federation(fed)
    runner = fl_driver._get_runner(fl, 4, 2, meta_for(fed, hidden=16), 2,
                                   stack)
    keys = jax.vmap(jax.random.key)(jnp.arange(2, dtype=jnp.uint32))
    hlo = runner.lower(keys, stack, size, quality,
                       fl_driver._params_lanes([fl], 2)).compile().as_text()
    _assert_every_op_in_a_phase(hlo)


def test_population_round_body_is_scoped():
    pop = make_population(0, n_clients=64, pool_samples=600,
                          members_per_client=8)
    fl = fl_driver.fl_for_method(
        FLConfig(n_clients=64, clients_per_round=4, k_max=4, rounds=4,
                 **FL), "proposed")
    runner = fl_driver._get_population_runner(
        fl, 4, 2, meta_for(pop, hidden=16), 2, pop, 1)
    keys = jax.vmap(jax.random.key)(jnp.arange(2, dtype=jnp.uint32))
    hlo = runner.lower(keys, pop,
                       fl_driver._params_lanes([fl], 2)).compile().as_text()
    _assert_every_op_in_a_phase(hlo)
    # the cohort's own passes are where the trace looks for them
    names = [name for _, name in _round_ops(hlo)]
    assert any("/selection/" in n and "cohort_topk" in n for n in names)
    assert any("/carry_update/" in n for n in names)


@pytest.mark.parametrize("traced_sigma", [False, True])
def test_dp_tree_splits_into_layout_noise_and_kernel(traced_sigma):
    tree = {"w": jnp.ones((42, 16)), "b": jnp.ones((16,))}

    def step(t, key, sigma):
        return ops.dp_clip_noise_tree(
            t, key, 1.0, sigma if traced_sigma else 0.5, interpret=True)

    hlo = jax.jit(step).lower(tree, jax.random.key(0),
                              jnp.float32(0.5)).compile().as_text()
    names = set(_OP_NAME.findall(hlo))
    for scope in ("dp_layout", "dp_kernel", "dp_noise"):
        assert any(scope in n.split("/") for n in names), scope
