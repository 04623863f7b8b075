"""End-to-end FL driver for the anomaly-detection use case (paper §V).

Runs the full Algorithm-1 loop on the (synthetic stand-in) UNSW-NB15 / ROAD
federations, producing the metrics the paper reports: accuracy, AUC-ROC and
(simulated) training time, for our method and the baselines.

The detector architecture is pluggable (ISSUE 4): every model-touching
site — init, per-client loss, test-set prediction, metrics, FedL2P
personalisation — goes through the :class:`~repro.models.spec.ModelSpec`
resolved from the STATIC ``FLConfig.model`` field (``mlp`` — the paper's
detector, default — or the window-native ROAD detectors ``cnn``/``rglru``).
Model choice rides the runner-cache statics key, so each architecture
compiles once and shares the sweep/privacy machinery unchanged.

Methods:
  proposed        — adaptive utility selection + DP + fault tolerance (ours)
  proposed_noft   — ours without fault tolerance      (Table II ablation)
  acfl            — ACFL-style uncertainty (active) selection [5]-lite
  fedl2p          — FedAvg + per-client personalisation fine-tuning [11]-lite
  random          — plain FedAvg with random selection
  adafl           — AdaFL-style history-weighted selection [3]-lite
  power_of_choice — power-of-choice selection

Execution engines (docs/ARCHITECTURE.md):

* :func:`run_fl_sweep` — the COMPILED sweep engine.  The whole round loop
  is one ``jax.lax.scan`` (batch sampling, round step, time model and eval
  all lowered), ``jax.vmap``-ed over a **seed×config lane axis**: every
  scalar hyper-parameter (``FLParams``) is a runtime array, so an entire
  ε/failure/lr grid × repeated trials runs as ONE program, compiled once
  per (method statics, shapes) and sharded over the available devices.
  There is no host sync until the final history readback.
* :func:`run_fl` / :func:`run_fl_batch` — single-cell front doors of the
  same engine (a sweep of one config; a batch of one seed).
* Scheduled-budget privacy (``FLConfig.dp_scheduled``): the privacy
  subsystem's RDP accountant + budget scheduler ride the scan carry —
  per-round σ from the scheduler, exhaustion masking via the round step's
  ``update_gate``, accounted ε in the eval trace (``repro/privacy``,
  docs/ARCHITECTURE.md §Privacy).
* Failure scenarios (``repro/fault``, docs/DESIGN.md §6): the runtime
  ``fault_process`` lane code selects iid / Markov-bursty /
  Weibull-lifetime / straggler failure processes; per-client process
  state rides in ``RoundState`` through the scan carry, stragglers feed
  per-client ``slow`` factors into :func:`simulate_round_time`, and the
  eval trace carries a ``fail`` history column.
* :func:`run_fl_legacy` — the original per-round Python loop, kept as the
  semantic oracle: tests/test_engine.py checks the scanned engine against
  it, and benchmarks/bench_engine.py records the old-vs-new rounds/sec
  comparison in BENCH_engine.json; BENCH_sweep.json records the
  sweep-vs-per-cell comparison (benchmarks/bench_sweep.py).

Time model (the container has one CPU; the paper measured a GPU workstation):
simulated round time = slowest selected client's local compute
(steps × base_step_time / compute_capacity_i) + communication + DP overhead
+ checkpoint writes + Weibull-expected recovery — every term is derived from
the same FLConfig/fault model the rest of the framework uses, so *relative*
times across methods are meaningful (EXPERIMENTS.md §Time-model reports
those).  :func:`simulate_round_time` is pure ``jnp`` so the accumulator can
ride inside the scan carry.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import (FLConfig, FLParams, fl_params, fl_static)
from repro.core import fault as fault_lib
from repro.core import plans as plans_lib
from repro.core import rounds as rounds_lib
from repro.core import scale as scale_lib
from repro.data.synthetic import (FederatedData, Population,
                                  StackedFederation, round_batches,
                                  sample_cohort_batches,
                                  sample_round_batches, stack_federation)
from repro.launch.mesh import make_scale_mesh
from repro.models import shardctx
from repro.models import sharding as shard_lib
from repro.obs import stats as obs_stats
from repro.obs import trace as obs_trace
from repro.models.mlp import auc_roc, auc_roc_jnp
from repro.models.spec import DataMeta, ModelSpec, get_model_spec, meta_for
from repro.privacy import accountant as acct_lib
from repro.privacy import schedule as sched_lib
from repro.privacy.accountant import accounted_epsilon

METHODS = ("proposed", "proposed_noft", "acfl", "fedl2p", "random", "adafl",
           "power_of_choice")


def fl_for_method(base: FLConfig, method: str) -> FLConfig:
    """Method-specific FLConfig tweaks (selection strategy etc.)."""
    if method == "proposed":
        return dataclasses.replace(base, selection="adaptive_utility",
                                   fault_tolerance=True)
    if method == "proposed_noft":
        return dataclasses.replace(base, selection="adaptive_utility",
                                   fault_tolerance=False)
    if method == "acfl":
        return dataclasses.replace(base, selection="acfl", adaptive_k=False)
    if method == "fedl2p":
        return dataclasses.replace(base, selection="random", adaptive_k=False)
    if method == "random":
        return dataclasses.replace(base, selection="random", adaptive_k=False)
    if method == "adafl":
        return dataclasses.replace(base, selection="adafl")
    if method == "power_of_choice":
        return dataclasses.replace(base, selection="power_of_choice",
                                   adaptive_k=False)
    raise ValueError(method)


@dataclass
class RunResult:
    method: str
    dataset: str
    seed: int
    accuracy: float
    auc: float
    sim_time_s: float
    wall_time_s: float
    rounds: int
    eps_spent: float
    history: Dict[str, List[float]] = field(default_factory=dict)
    # final global model (host numpy pytree), attached only when the caller
    # asked (run_fl*(..., return_params=True)) — the serving engine's input
    params: Optional[object] = field(default=None, repr=False)

    def time_to_acc(self, target: float) -> float:
        """Simulated seconds until test accuracy first reaches ``target``
        (the paper's training-time metric is time-to-quality); inf if never."""
        for t, a in zip(self.history.get("cum_time", []), self.history.get("acc", [])):
            if a >= target:
                return t
        return float("inf")


def personalized_client_params(params, fed: FederatedData, spec: ModelSpec,
                               steps: int = 3, lr: float = 0.05,
                               batch: int = 64, seed: int = 0) -> List:
    """FedL2P-lite fine-tuning, parameters only: a few local SGD steps per
    client from the global ``params``; returns one personalised pytree per
    client (client order).  The rng draws happen exclusively here, in
    client order, so splitting metrics out (``_personalize``) or exporting
    the params for serving (``export_personalized``) is draw-for-draw
    identical to the original fused loop."""
    rng = np.random.default_rng(seed)
    grad_fn = jax.jit(jax.grad(spec.loss))
    out = []
    for ci in range(fed.n_clients):
        p = params
        for _ in range(steps):
            idx = rng.integers(0, len(fed.x[ci]), batch)
            b = {"x": jnp.asarray(fed.x[ci][idx]), "y": jnp.asarray(fed.y[ci][idx])}
            g = grad_fn(p, b)
            p = jax.tree.map(lambda a, gg: a - lr * gg, p, g)
        out.append(p)
    return out


def export_personalized(params, fed: FederatedData, spec: ModelSpec,
                        steps: int = 3, lr: float = 0.05,
                        batch: int = 64, seed: int = 0):
    """Personalised per-client parameters STACKED along a leading client
    axis (host numpy) — the ``heads`` pytree the serving engine indexes
    with ``client=i`` and ``save_serving_checkpoint`` persists."""
    per_client = personalized_client_params(params, fed, spec, steps=steps,
                                            lr=lr, batch=batch, seed=seed)
    return jax.tree.map(lambda *leaves: np.stack([np.asarray(l) for l in leaves]),
                        *per_client)


def _personalize(params, fed: FederatedData, spec: ModelSpec,
                 steps: int = 3, lr: float = 0.05,
                 batch: int = 64, seed: int = 0):
    """FedL2P-lite personalisation: a few local fine-tune steps per client;
    returns the average personalised test metrics.  Model-generic: the
    fine-tune gradient and the test metrics come from the ``spec``."""
    per_client = personalized_client_params(params, fed, spec, steps=steps,
                                            lr=lr, batch=batch, seed=seed)
    accs, scores_all = [], []
    for p in per_client:
        proba = spec.predict_proba(p, jnp.asarray(fed.test_x))[:, 1]
        accs.append(float(spec.accuracy(p, jnp.asarray(fed.test_x),
                                        jnp.asarray(fed.test_y))))
        scores_all.append(np.asarray(proba))
    acc = float(np.mean(accs))
    auc = auc_roc(np.mean(scores_all, axis=0), fed.test_y)
    return acc, auc


def simulate_round_time(fl: FLConfig, util_state, sel_mask, failed,
                        base_step_time: float = 0.02,
                        comm_time: float = 0.35,
                        ckpt_write: float = 0.08,
                        param_kb: float = 64.0,
                        params: Optional[FLParams] = None,
                        slow=None) -> jnp.ndarray:
    """Paper-faithful wall-time model for one round (see module docstring).

    Pure ``jnp`` — jit-safe, so the cumulative simulated time is carried
    through the ``lax.scan`` state instead of syncing to NumPy every round.
    Branching on the STATIC FLConfig fields (dp_enabled, fault_tolerance)
    is fine; the recovery term reads the runtime ``params`` (defaulting to
    the config's values), so failure-model sweeps share one program.

    ``slow``: optional [n] per-client round-time stretch factors from the
    failure-scenario engine (``RoundMetrics.slow`` — the straggler process;
    all-ones on every other lane, where ``x·1.0`` is bitwise ``x``).  The
    round waits for the slowest selected client, so one straggler stretches
    the whole cohort's round — exactly the synchronous-FL pathology.

    Plan time models (DESIGN.md §4) ride the runtime ``plan_code`` lane,
    branch-free like everything else, so a mixed plan frontier shares the
    program and code-0 lanes stay bitwise the synchronous model:

    * ``buffered_async`` (code 1) — the server flushes once K =
      ``async_buffer`` updates arrive, so the round costs the K-th
      smallest per-client compute time (capped at the slowest when fewer
      than K contribute) + communication; checkpoint writes and recovery
      leave the critical path (the server never waits for dead clients).
    * ``hierarchical`` (code 2) — slowest client + two cheaper hops
      (client→edge and edge→cloud, each ``hier_comm_frac`` of the flat
      WAN hop) instead of the flat client→cloud communication.
    """
    pr = fl_params(fl) if params is None else params
    sel = sel_mask > 0
    any_sel = jnp.any(sel)
    steps = fl.local_epochs
    compute = steps * base_step_time / jnp.maximum(util_state.compute, 0.1)
    if slow is not None:
        compute = compute * slow
    slowest = jnp.max(jnp.where(sel, compute, 0.0))
    comm_full = comm_time * (1.0 + param_kb / 1024.0)

    # Synchronous chain — textually the pre-registry expression.  The plan
    # variants are selected AFTER the chain (not interleaved into it) so
    # XLA constant-folds the scalar additions exactly as it always did and
    # code-0 lanes stay bitwise (tests/test_plans.py golden pins).
    t = slowest + comm_full
    if fl.dp_enabled:
        t = t + 0.01  # clip+noise pass
    n_failed_sel = jnp.sum(jnp.where(sel, failed, 0.0))
    if fl.fault_tolerance:
        t = t + ckpt_write * max(1, steps // 2)
        t = t + n_failed_sel * fault_lib.recovery_overhead(pr.recovery_time)
    else:
        # failed clients redo the whole round next time: amortised penalty
        t = t + n_failed_sel * slowest

    # buffered_async (code 1): K-th smallest selected arrival — the same
    # per-client compute vector, straggler-stretched, so arrival ORDER is
    # the failure-scenario engine's (repro.fault.arrival_score ranks agree;
    # capped at the slowest when fewer than K contribute).  No checkpoint
    # or recovery stall: the buffer flushes without waiting on the dead.
    arrivals = jnp.sort(jnp.where(sel, compute, jnp.inf))
    k_idx = jnp.clip(pr.async_buffer, 1.0,
                     float(sel_mask.shape[0])).astype(jnp.int32) - 1
    kth = jnp.minimum(jnp.take(arrivals, k_idx), slowest)
    t_async = kth + comm_full
    if fl.dp_enabled:
        t_async = t_async + 0.01

    # hierarchical (code 2): same synchronous chain, but the flat WAN hop
    # is replaced by two edge hops each at hier_comm_frac of its cost
    t_hier = t - comm_full + 2.0 * pr.hier_comm_frac * comm_full

    t = jnp.where(pr.plan_code == 1.0, t_async,
                  jnp.where(pr.plan_code == 2.0, t_hier, t))
    return jnp.where(any_sel, t, comm_time)


def spent_epsilon(fl: FLConfig, rounds: int) -> float:
    """Deprecated alias of :func:`repro.privacy.accounted_epsilon` (PR 3).

    The accountant subsystem is the single source of ε now: fixed-σ runs
    report the closed-form composition, scheduled runs report the in-scan
    accountant's trace (``RunResult.history['eps']``)."""
    warnings.warn(
        "fl_driver.spent_epsilon is deprecated; use "
        "repro.privacy.accounted_epsilon (fixed-σ) or the in-scan "
        "accountant trace (dp_scheduled)", DeprecationWarning, stacklevel=2)
    return accounted_epsilon(fl, rounds)


# ---------------------------------------------------------------------------
# Compiled engine: lax.scan over rounds, vmap over seed×config lanes
# ---------------------------------------------------------------------------


def _eval_rounds(rounds: int, eval_every: int) -> List[int]:
    """0-based round indices the legacy loop evaluated at."""
    return [r for r in range(rounds)
            if (r + 1) % eval_every == 0 or r == rounds - 1]


def realized_cohort_fraction(k_eff, n_clients: int):
    """Sampling fraction the RDP accountant must compose at.

    ``_topk_mask`` selects every rank strictly below ``k_eff`` — for a
    fractional controller K (adaptive-K grow steps produce e.g. 7.75) that
    is ``ceil(k_eff)`` clients, so composing at ``k_eff/n`` systematically
    under-accounted ε (ISSUE 4 bugfix).  ``ceil(k_eff)/n`` is the realised
    cohort's fraction; availability masking can only select *fewer*
    clients, so this never understates the spend.
    """
    return jnp.clip(jnp.ceil(k_eff) / n_clients, 0.0, 1.0)


def _build_single_run(fl: FLConfig, rounds: int, eval_every: int,
                      meta: DataMeta):
    """``single_run(key, stack, data_size, data_quality, params) ->
    (final_params, sim_time, eval trace)``, a pure function of the seed key,
    the (runtime-argument) federation and the runtime :class:`FLParams`.

    ``fl`` here is the STATIC config (the caller canonicalises with
    ``fl_static``): every scalar hyper-parameter the round step consumes
    comes from ``params``, so vmapping this function over stacked FLParams
    lanes sweeps a whole hyper-parameter grid inside one program.  The
    detector architecture is the spec resolved from the STATIC
    ``fl.model`` against ``meta`` (models/spec.py) — init, per-client
    loss and the eval metrics all come from it, so a new architecture is
    a registry entry, not an engine change.

    Structure: a NESTED scan.  The inner ``lax.scan`` advances ``eval_every``
    rounds carrying (RoundState, data key, cumulative simulated time); the
    outer scan runs one inner block per eval point and computes test
    accuracy/AUC once per block — the same eval cadence as the legacy loop,
    so the compiled engine never pays per-round eval (the test-set forward +
    rank-AUC argsort are ~half a round's compute).  A trailing partial block
    handles ``rounds % eval_every`` so the final round is always evaluated.

    Scheduled-budget configs (``fl.dp_scheduled``, STATIC) extend the carry
    with the privacy subsystem's state: an in-scan RDP
    :class:`~repro.privacy.accountant.AccountantState` and a
    :class:`~repro.privacy.schedule.SchedulerState`.  Every round the
    scheduler emits σ_t, the accountant tentatively composes the release at
    the REALISED cohort fraction q_t = ceil(k_eff)/n (adaptive K changes
    the subsampling amplification and the accountant sees it; the top-k
    mask selects ceil of the controller's fractional K — see
    :func:`realized_cohort_fraction`), and a release
    that would push ε past ``pr.dp_budget`` is withheld via the round
    step's ``update_gate`` — the global model freezes bitwise at budget
    exhaustion.  ε is converted from the carried RDP curve on eval
    boundaries only and emitted into the trace (``eps``/``sigma``/``live``
    history columns); the scheduler's stall controller also updates there,
    from the same AUC the eval computes anyway.
    """
    n_full, rem = divmod(rounds, eval_every)
    scheduled = fl.dp_enabled and fl.dp_scheduled
    if scheduled and fl.dp_mode != "clipped":
        raise ValueError(
            "dp_scheduled requires dp_mode='clipped': the accountant "
            "composes z_t = sigma_t/dp_clip, which is only a valid "
            "(epsilon, delta) statement when updates are clipped to "
            "dp_clip — the paper's unclipped fixed-sigma mode has "
            "unbounded sensitivity")

    spec = get_model_spec(fl.model, meta)

    # ``fl`` is canonicalised (fl_static: plan → program family), so the
    # registry hands back the family's round builder; plans outside the
    # client_parallel family have their own drivers and fail loudly here
    # instead of silently running the wrong program (pre-registry, a
    # client_serial config fell through to the parallel round step).
    plan = plans_lib.get_plan(fl.plan)
    if plan.family != "client_parallel":
        raise ValueError(
            f"the compiled sweep engine runs the 'client_parallel' program "
            f"family; plan {fl.plan!r} (family {plan.family!r}) is not "
            "driver-capable — see the core/plans registry for its engine")
    builder = plan.builder_fn()

    def single_run(key, stack: StackedFederation, data_size, data_quality,
                   pr: FLParams):
        n_clients = stack.n_clients
        round_step = builder(spec.loss, fl, n_clients)
        tx, ty = stack.test_x, stack.test_y
        k_static = jnp.asarray(float(fl.clients_per_round), jnp.float32)

        def one_round(carry, _):
            if scheduled:
                state, data_key, cum_time, acct, sched = carry
            else:
                state, data_key, cum_time = carry
            # phase scopes of the round loop around the round step
            # (core/rounds.py names the step's own): metadata only
            with jax.named_scope("batch_sample"):
                data_key, k_batch = jax.random.split(data_key)
                batches = sample_round_batches(k_batch, stack,
                                               fl.local_epochs, fl.local_batch)
            if scheduled:
                with jax.named_scope("bookkeeping"):
                    k_eff = state.kctl.k if fl.adaptive_k else k_static
                    # compose at the REALISED cohort fraction — _topk_mask
                    # selects ceil(k_eff) clients, not k_eff
                    q_t = realized_cohort_fraction(k_eff, n_clients)
                    z_t = sched_lib.scheduled_multiplier(
                        sched, pr, state.round_idx, rounds)
                    sigma_t = z_t * pr.dp_clip
                    acct_next = acct_lib.accountant_step(acct, z_t, q_t)
                    eps_next = acct_lib.epsilon_from_state(acct_next,
                                                           fl.dp_delta)
                    live = (eps_next <= pr.dp_budget).astype(jnp.float32)
                state, m = round_step(state, batches,
                                      pr._replace(dp_sigma=sigma_t),
                                      update_gate=live)
            else:
                state, m = round_step(state, batches, pr)
            with jax.named_scope("bookkeeping"):
                if scheduled:
                    # spend the budget only for released rounds
                    acct = jax.tree.map(
                        lambda n, o: jnp.where(live > 0, n, o), acct_next,
                        acct)
                cum_time = cum_time + simulate_round_time(
                    fl, state.util, m.sel_mask, m.failed, params=pr,
                    slow=m.slow)
                fail_mean = jnp.mean(m.failed)
            if scheduled:
                return ((state, data_key, cum_time, acct, sched),
                        (m.global_loss, m.k_effective, fail_mean, sigma_t,
                         live))
            return ((state, data_key, cum_time),
                    (m.global_loss, m.k_effective, fail_mean))

        def eval_block(carry, block_len):
            carry, ys = jax.lax.scan(one_round, carry, None,
                                     length=block_len)
            if scheduled:
                state, data_key, cum_time, acct, sched = carry
                losses, ks, fails, sigmas, lives = ys
            else:
                state, _, cum_time = carry
                losses, ks, fails = ys
            # metadata-only phase marker: tags the eval ops in profiler
            # traces / HLO names without touching the lowered math
            with jax.named_scope("eval_block"):
                acc = spec.accuracy(state.params, tx, ty)
                proba = spec.predict_proba(state.params, tx)[:, 1]
                auc = auc_roc_jnp(proba, ty)
            trace = {
                "loss": losses[-1],
                "acc": acc,
                "auc": auc,
                "k": ks[-1],
                "fail": fails[-1],
                "cum_time": cum_time,
            }
            if scheduled:
                trace["eps"] = acct_lib.epsilon_from_state(acct, fl.dp_delta)
                trace["sigma"] = sigmas[-1]
                trace["live"] = jnp.mean(lives)
                sched = sched_lib.scheduler_update(sched, auc, pr)
                carry = (state, data_key, cum_time, acct, sched)
            return carry, trace

        # param_axes sharding hook: identity outside a shardctx context
        # (every unsharded program lowers unchanged); under the context
        # run_fl_population installs for over-budget models, this seeds the
        # GSPMD layout of the whole round scan from the spec's declared
        # logical axes.
        params = spec.constrain_params(spec.init(jax.random.fold_in(key, 0)))
        state = rounds_lib.init_round_state(
            params, fl, jax.random.fold_in(key, 1), n_clients=n_clients,
            data_size=data_size, data_quality=data_quality,
        )
        carry = (state, jax.random.fold_in(key, 2), jnp.zeros((), jnp.float32))
        if scheduled:
            q_nom = jnp.asarray(min(fl.clients_per_round / n_clients, 1.0),
                                jnp.float32)
            carry = carry + (
                acct_lib.init_accountant_state(),
                sched_lib.init_scheduler(pr.dp_budget, fl.dp_delta, rounds,
                                         q_nom),
            )
        trace = None
        if n_full:
            carry, trace = jax.lax.scan(
                lambda c, _: eval_block(c, eval_every), carry, None,
                length=n_full)
        if rem:
            carry, tail = eval_block(carry, rem)
            tail = jax.tree.map(lambda x: x[None], tail)
            trace = tail if trace is None else jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), trace, tail)
        state, _, sim_time = carry[:3]
        return state.params, sim_time, trace

    return single_run


# Compiled runners keyed on (STATIC config, rounds, eval_every, DataMeta,
# n_lanes, stack shapes): the federation AND every scalar hyper-parameter
# (FLParams) are runtime arguments, so ONE program serves an entire
# ε/failure/lr grid — one compile per (method-statics, shapes) cell, not
# per grid point.  The STATIC config includes ``FLConfig.model``, so each
# detector architecture gets its own program and a model × seed grid
# compiles once per model.  RUNNER_STATS counts misses/hits so tests and
# benchmarks can assert the single-compile property.
_RUNNER_CACHE: Dict = {}
# A view of the unified registry (repro.obs.stats) — dict-style call sites
# (index, +=, dict(...)) work unchanged; STATS.snapshot()/reset()/expect()
# see it as the "runner" namespace.
RUNNER_STATS = obs_stats.STATS.counters("runner", misses=0, hits=0)

# Device-side federations cached per host FederatedData object, so repeat
# calls (seed loops, epsilon sweeps) skip the O(n_clients × max_n × d)
# re-pad, re-upload and row-table build that stack_federation performs.
# Keyed by id() with a weakref guard (FederatedData defines __eq__, so it
# is unhashable); dead entries are evicted by the weakref callback.
_STACK_CACHE: Dict[int, tuple] = {}


def _device_federation(fed: FederatedData):
    key = id(fed)
    entry = _STACK_CACHE.get(key)
    if entry is None or entry[0]() is not fed:
        sizes = fed.data_sizes()
        ref = weakref.ref(fed, lambda _: _STACK_CACHE.pop(key, None))
        entry = (ref, stack_federation(fed),
                 jnp.asarray(sizes / sizes.mean()),
                 jnp.asarray(fed.label_entropy()))
        _STACK_CACHE[key] = entry
    return entry[1], entry[2], entry[3]


def _get_runner(fl: FLConfig, rounds: int, eval_every: int, meta: DataMeta,
                n_lanes: int, stack: StackedFederation,
                sharded: bool = False):
    """Compiled ``runner(keys[L], stack, data_size, data_quality,
    params_lanes[L]) -> (params[L], sim_time[L], trace[L])``.

    Keyed on the STATIC config (which includes ``model``) only: two configs
    that differ in runtime knobs (ε, failure prob, lrs, ...) resolve to the
    same cache entry and the same XLA program.  A ``sharded`` runner binds
    its lane axis to the ``lane`` axis of the mesh it is traced under
    (``spmd_axis_name``): each device runs its ``L/N`` lanes whole, with no
    collective, and the Pallas kernels' ``shard_map``
    (``kernels/ops.py::per_device``) gets that device's lanes only.
    Nothing is donated: on the chip XLA reports the per-lane inputs (keys +
    FLParams) as unusable for donation — no output has their shape.
    """
    static = fl_static(fl)
    cache_key = (static, rounds, eval_every, meta, n_lanes, stack.shapes(),
                 sharded)
    runner = _RUNNER_CACHE.get(cache_key)
    if runner is None:
        RUNNER_STATS["misses"] += 1
        obs_trace.event("compile.runner_miss", engine="sweep",
                        model=static.model, rounds=rounds,
                        n_lanes=n_lanes, cache_size=len(_RUNNER_CACHE))
        with obs_trace.span("runner.build", engine="sweep",
                            model=static.model):
            single_run = _build_single_run(static, rounds, eval_every, meta)
            runner = jax.jit(jax.vmap(
                single_run, in_axes=(0, None, None, None, 0),
                spmd_axis_name="lane" if sharded else None))
        _RUNNER_CACHE[cache_key] = runner
    else:
        RUNNER_STATS["hits"] += 1
    return runner


def _lane_mesh(n_lanes: int) -> Optional[jax.sharding.Mesh]:
    """1-D ``lane`` mesh over the devices, or ``None`` on a single device.
    The caller pads the lane axis up to a multiple of the mesh size
    (duplicating trailing lanes, dropped on readback) so every device
    carries whole lanes — a 17-lane sweep on 16 devices runs two waves
    instead of falling back to one device."""
    devices = jax.devices()
    n = min(len(devices), n_lanes)
    if n <= 1:
        return None
    return jax.sharding.Mesh(np.asarray(devices[:n]), ("lane",))


def _sweep_cells(fl: FLConfig, params_grid: Sequence, method: str,
                 capability: str = "driver_capable") -> List[FLConfig]:
    """Resolve a params_grid into per-cell FLConfigs sharing ``fl``'s
    statics (shared by the sweep and population engines).

    Each cell's plan is resolved against the core/plans registry and must
    carry ``capability`` (``driver_capable`` for the dense engines,
    ``cohort_capable`` for the population engine).  Plans in the same
    *family* (``fl_static`` canonicalises plan → family) may differ across
    cells — that is how a mixed sync/async/hierarchical frontier rides the
    ``plan_code`` lane of one compiled program.
    """
    cells: List[FLConfig] = []
    for p in params_grid:
        if isinstance(p, FLConfig):
            cell = fl_for_method(p, method)
        elif isinstance(p, FLParams):
            # plan_code is derived from FLConfig.plan, not a config field:
            # map a differing code back to the registered plan name
            overrides = p._asdict()
            code = float(overrides.pop("plan_code"))
            cell = dataclasses.replace(fl, **overrides)
            if code != plans_lib.plan_code(cell.plan):
                cell = dataclasses.replace(
                    cell, plan=plans_lib.plan_for_code(
                        plans_lib.plan_family(cell.plan), code))
        else:
            cell = dataclasses.replace(fl, **dict(p))
        if not getattr(plans_lib.get_plan(cell.plan), capability):
            raise ValueError(
                f"plan {cell.plan!r} cannot run on this engine: the "
                f"core/plans registry marks it {capability}=False")
        if fl_static(cell) != fl_static(fl):
            raise ValueError(
                "params_grid cell differs from the base config in a STATIC "
                "field — those gate code structure and cannot ride the "
                f"runtime lane axis: {cell}")
        cells.append(cell)
    return cells


def _params_lanes(cells: Sequence[FLConfig], n_seeds: int) -> FLParams:
    """Stack each cell's runtime params into [n_cells·n_seeds] f32 lanes
    (cell-major: lane = cell_index * n_seeds + seed_index)."""
    per_cell = [fl_params(c) for c in cells]
    return jax.tree.map(
        lambda *xs: jnp.repeat(jnp.asarray(xs, jnp.float32), n_seeds),
        *per_cell)


def run_fl_sweep(
    fed: FederatedData,
    fl: FLConfig,
    params_grid: Sequence,
    seeds: Sequence[int] = (0, 1, 2, 3),
    method: str = "proposed",
    rounds: Optional[int] = None,
    eval_every: int = 10,
    dataset: str = "unsw",
    hidden: int = 64,
    return_params: bool = False,
) -> List[List[RunResult]]:
    """An entire hyper-parameter sweep as ONE compiled program.

    ``params_grid``: one entry per sweep cell — an :class:`FLConfig` sharing
    ``fl``'s statics, a dict of runtime-field overrides applied to ``fl``
    (e.g. ``{"dp_epsilon": 0.1}``), or an :class:`FLParams`.  The engine
    stacks every cell's runtime scalars into a **seed×config lane axis**
    (``len(params_grid) · len(seeds)`` lanes), vmaps the scanned round loop
    over it, and shards the lane axis across the available devices
    (a 1-D ``lane`` mesh — on one device the program is identical, on N
    devices each runs ``lanes/N`` trials whole, with no collective).

    One ``_get_runner`` miss covers the WHOLE grid (the cache keys on
    statics + shapes, not cell values): a Fig.-3 ε column or a Table-II
    failure sweep compiles once and then runs every cell·seed lane in a
    single device program.  Lane semantics match the per-cell engine —
    ``run_fl_sweep(..., [cfg_a, cfg_b], seeds)[i][j]`` equals
    ``run_fl(fed, cfg_i, seed=seeds[j])`` lane for lane (tested in
    tests/test_sweep.py).

    Returns results indexed ``[cell][seed]``.
    """
    fl = fl_for_method(fl, method)
    rounds = int(rounds or fl.rounds)
    seeds = [int(s) for s in seeds]
    cells = _sweep_cells(fl, params_grid, method)
    if not cells:
        return []

    n_lanes = len(cells) * len(seeds)
    mesh = _lane_mesh(n_lanes)
    n_padded = n_lanes
    if mesh is not None:
        n_padded = -(-n_lanes // mesh.size) * mesh.size

    t0 = time.time()
    with obs_trace.span("sweep.prepare", method=method, n_lanes=n_lanes,
                        n_cells=len(cells), rounds=rounds,
                        plans=",".join(sorted({c.plan for c in cells}))):
        meta = meta_for(fed, hidden=hidden)
        stack, data_size, data_quality = _device_federation(fed)
        runner = _get_runner(fl, rounds, eval_every, meta, n_padded, stack,
                             sharded=mesh is not None)
        keys = jax.vmap(jax.random.key)(
            jnp.asarray(np.tile(seeds, len(cells)), jnp.uint32))
        lanes = _params_lanes(cells, len(seeds))
        if n_padded > n_lanes:
            pad = n_padded - n_lanes
            keys = jnp.concatenate([keys, jnp.repeat(keys[-1:], pad, axis=0)])
            lanes = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.repeat(x[-1:], pad, axis=0)]),
                lanes)

        if mesh is not None:
            s_lane = NamedSharding(mesh, PartitionSpec("lane"))
            s_rep = NamedSharding(mesh, PartitionSpec())
            keys = jax.device_put(keys, s_lane)
            lanes = jax.tree.map(lambda x: jax.device_put(x, s_lane), lanes)
            stack, data_size, data_quality = jax.tree.map(
                lambda x: jax.device_put(x, s_rep),
                (stack, data_size, data_quality))

    # the Pallas kernels, which XLA cannot partition, read this mesh to run
    # per device (kernels/ops.py::per_device)
    mesh_ctx = (jax.set_mesh(mesh) if mesh is not None
                else contextlib.nullcontext())
    with obs_trace.span("sweep.execute", n_lanes=n_lanes), mesh_ctx:
        params_b, sim_b, trace_b = runner(keys, stack, data_size,
                                          data_quality, lanes)
        jax.block_until_ready(sim_b)
    wall_per_lane = (time.time() - t0) / max(n_lanes, 1)

    eval_idx = _eval_rounds(rounds, eval_every)
    with obs_trace.span("sweep.readback", n_lanes=n_lanes):
        trace_np = {k: np.asarray(v) for k, v in trace_b.items()}
        sim_np = np.asarray(sim_b)
    with obs_trace.span("sweep.results", n_lanes=n_lanes):
        # one spec for every lane (model is static) — rebuilding per lane
        # would defeat _personalize's jit cache for closure-built specs
        spec = get_model_spec(fl.model, meta) if method == "fedl2p" else None
        out: List[List[RunResult]] = []
        for ci, cell in enumerate(cells):
            # fixed-σ cells: host closed-form composition (engine-
            # independent); scheduled cells: ε comes from the lane's in-scan
            # accountant trace
            scheduled = cell.dp_enabled and cell.dp_scheduled
            eps_cell = None if scheduled else accounted_epsilon(cell, rounds)
            row = []
            for si, seed in enumerate(seeds):
                lane = ci * len(seeds) + si
                history = {"round": [r + 1 for r in eval_idx]}
                for name in trace_np:
                    history[name] = [float(x) for x in trace_np[name][lane]]
                eps = history["eps"][-1] if scheduled else eps_cell
                sim_time = float(sim_np[lane])
                acc, auc = history["acc"][-1], history["auc"][-1]
                if method == "fedl2p":
                    # personalisation pass (the point of FedL2P) + its
                    # simulated cost
                    acc, auc = _personalize(
                        jax.tree.map(lambda x: x[lane], params_b), fed, spec,
                        seed=seed)
                    sim_time *= 1.2
                lane_params = None
                if return_params:
                    lane_params = jax.tree.map(lambda x: np.asarray(x[lane]),
                                               params_b)
                row.append(RunResult(
                    method=method, dataset=dataset, seed=seed,
                    accuracy=acc, auc=auc,
                    sim_time_s=sim_time, wall_time_s=wall_per_lane,
                    rounds=rounds, eps_spent=eps, history=history,
                    params=lane_params,
                ))
            out.append(row)
    return out


def run_fl_batch(
    fed: FederatedData,
    fl: FLConfig,
    method: str = "proposed",
    seeds: Sequence[int] = (0, 1, 2, 3),
    rounds: Optional[int] = None,
    eval_every: int = 10,
    dataset: str = "unsw",
    hidden: int = 64,
    return_params: bool = False,
) -> List[RunResult]:
    """All repeated trials of one (method, dataset) cell as ONE compiled
    program: a single-cell :func:`run_fl_sweep` (vmap over the seed lanes).

    Per-seed results are bit-for-bit the batched lanes of the single-seed
    scanned engine (each lane keys off ``jax.random.key(seed)``), so
    ``run_fl_batch(seeds=[a, b])`` ≈ ``[run_fl(seed=a), run_fl(seed=b)]``
    at a fraction of the dispatch cost.  ``wall_time_s`` on each result is
    the batch wall time amortised over the seeds.
    """
    return run_fl_sweep(fed, fl, [fl], seeds=seeds, method=method,
                        rounds=rounds, eval_every=eval_every, dataset=dataset,
                        hidden=hidden, return_params=return_params)[0]


def run_fl(
    fed: FederatedData,
    fl: FLConfig,
    method: str = "proposed",
    seed: int = 0,
    rounds: Optional[int] = None,
    eval_every: int = 10,
    dataset: str = "unsw",
    hidden: int = 64,
    return_params: bool = False,
) -> RunResult:
    """Single-seed front door of the compiled engine (a batch of one)."""
    return run_fl_batch(fed, fl, method, seeds=(seed,), rounds=rounds,
                        eval_every=eval_every, dataset=dataset,
                        hidden=hidden, return_params=return_params)[0]


# ---------------------------------------------------------------------------
# Population engine (ISSUE 6): cohort training over a sharded client axis
# ---------------------------------------------------------------------------


def _build_population_run(fl: FLConfig, rounds: int, eval_every: int,
                          meta: DataMeta, sel_chunks: int):
    """``single_run(key, pop, params) -> (final_params, sim_time, trace)``
    over a :class:`~repro.data.synthetic.Population` — the population-scale
    sibling of :func:`_build_single_run` (ARCHITECTURE.md §Scale).

    Same nested-scan structure, same scheduled-privacy carry, but the round
    step is the ``client_cohort`` plan
    (:func:`repro.core.rounds.make_cohort_round`): per-round COMPUTE is
    O(k_max) — only the top-k cohort's data/state is gathered to the
    compute lanes — while O(N) work is limited to elementwise vector ops
    that shard over the ``client`` mesh axis.  Every per-round emission is
    a SCALAR (loss, k, population failure fraction, σ, live): at 10^5+
    clients an [N]-shaped ys column would dominate memory, so the per-round
    trace never materialises the population axis.

    The cohort time model waits for the slowest *selected* client:
    :func:`simulate_round_time` reads compute capacities through a
    cohort-gathered view of the utility state, with the cohort-shaped
    ``take``/``failed``/``slow`` columns from :class:`CohortMetrics`.
    """
    n_full, rem = divmod(rounds, eval_every)
    scheduled = fl.dp_enabled and fl.dp_scheduled
    if scheduled and fl.dp_mode != "clipped":
        raise ValueError(
            "dp_scheduled requires dp_mode='clipped': the accountant "
            "composes z_t = sigma_t/dp_clip, which is only a valid "
            "(epsilon, delta) statement when updates are clipped to dp_clip")
    spec = get_model_spec(fl.model, meta)
    k_cap = float(int(fl.k_max))

    def single_run(key, pop: Population, pr: FLParams):
        n_clients = pop.n_clients

        def sample_fn(k, p, idx):
            return sample_cohort_batches(k, p, idx, fl.local_epochs,
                                         fl.local_batch)

        round_step = rounds_lib.make_cohort_round(
            spec.loss, fl, n_clients, sample_fn, sel_chunks=sel_chunks)
        tx, ty = pop.test_x, pop.test_y
        k_static = jnp.asarray(float(fl.clients_per_round), jnp.float32)

        def one_round(carry, _):
            if scheduled:
                state, data_key, cum_time, acct, sched = carry
            else:
                state, data_key, cum_time = carry
            # phase scopes as in _build_single_run; the cohort's own
            # batch_sample scope sits inside make_cohort_round
            with jax.named_scope("batch_sample"):
                data_key, k_batch = jax.random.split(data_key)
            if scheduled:
                with jax.named_scope("bookkeeping"):
                    k_eff = state.kctl.k if fl.adaptive_k else k_static
                    # the cohort plan caps the controller at the static
                    # cohort size, so the accountant must see the same k
                    k_eff = jnp.minimum(k_eff, k_cap)
                    q_t = realized_cohort_fraction(k_eff, n_clients)
                    z_t = sched_lib.scheduled_multiplier(
                        sched, pr, state.round_idx, rounds)
                    sigma_t = z_t * pr.dp_clip
                    acct_next = acct_lib.accountant_step(acct, z_t, q_t)
                    eps_next = acct_lib.epsilon_from_state(acct_next,
                                                           fl.dp_delta)
                    live = (eps_next <= pr.dp_budget).astype(jnp.float32)
                state, m = round_step(state, pop, k_batch,
                                      pr._replace(dp_sigma=sigma_t),
                                      update_gate=live)
            else:
                state, m = round_step(state, pop, k_batch, pr)
            with jax.named_scope("bookkeeping"):
                if scheduled:
                    acct = jax.tree.map(
                        lambda a, o: jnp.where(live > 0, a, o), acct_next,
                        acct)
                util_view = state.util._replace(
                    compute=state.util.compute[m.cohort_idx])
                cum_time = cum_time + simulate_round_time(
                    fl, util_view, m.take, m.failed, params=pr, slow=m.slow)
            if scheduled:
                return ((state, data_key, cum_time, acct, sched),
                        (m.global_loss, m.k_effective, m.fail_frac, sigma_t,
                         live))
            return ((state, data_key, cum_time),
                    (m.global_loss, m.k_effective, m.fail_frac))

        def eval_block(carry, block_len):
            carry, ys = jax.lax.scan(one_round, carry, None, length=block_len)
            if scheduled:
                state, data_key, cum_time, acct, sched = carry
                losses, ks, fails, sigmas, lives = ys
            else:
                state, _, cum_time = carry
                losses, ks, fails = ys
            # metadata-only phase marker: tags the eval ops in profiler
            # traces / HLO names without touching the lowered math
            with jax.named_scope("eval_block"):
                acc = spec.accuracy(state.params, tx, ty)
                proba = spec.predict_proba(state.params, tx)[:, 1]
                auc = auc_roc_jnp(proba, ty)
            trace = {
                "loss": losses[-1],
                "acc": acc,
                "auc": auc,
                "k": ks[-1],
                "fail": fails[-1],
                "cum_time": cum_time,
            }
            if scheduled:
                trace["eps"] = acct_lib.epsilon_from_state(acct, fl.dp_delta)
                trace["sigma"] = sigmas[-1]
                trace["live"] = jnp.mean(lives)
                sched = sched_lib.scheduler_update(sched, auc, pr)
                carry = (state, data_key, cum_time, acct, sched)
            return carry, trace

        # param_axes sharding hook (see _build_single_run): a no-op unless
        # run_fl_population traced this program under a shardctx context
        params = spec.constrain_params(spec.init(jax.random.fold_in(key, 0)))
        state = rounds_lib.init_round_state(
            params, fl, jax.random.fold_in(key, 1), n_clients=n_clients,
            data_size=pop.data_size, data_quality=pop.data_quality,
        )
        carry = (state, jax.random.fold_in(key, 2), jnp.zeros((), jnp.float32))
        if scheduled:
            q_nom = jnp.asarray(
                min(min(fl.clients_per_round, int(fl.k_max)) / n_clients, 1.0),
                jnp.float32)
            carry = carry + (
                acct_lib.init_accountant_state(),
                sched_lib.init_scheduler(pr.dp_budget, fl.dp_delta, rounds,
                                         q_nom),
            )
        trace = None
        if n_full:
            carry, trace = jax.lax.scan(
                lambda c, _: eval_block(c, eval_every), carry, None,
                length=n_full)
        if rem:
            carry, tail = eval_block(carry, rem)
            tail = jax.tree.map(lambda x: x[None], tail)
            trace = tail if trace is None else jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), trace, tail)
        state, _, sim_time = carry[:3]
        return state.params, sim_time, trace

    return single_run


def _get_population_runner(fl: FLConfig, rounds: int, eval_every: int,
                           meta: DataMeta, n_lanes: int, pop: Population,
                           sel_chunks: int, model_shard_key=None,
                           sharded: bool = False):
    """Compiled ``runner(keys[L], pop, params_lanes[L])`` for the population
    engine.  Shares ``_RUNNER_CACHE``/``RUNNER_STATS`` with the dense sweep
    engine (a "pop" tag keeps the key spaces disjoint), so the
    single-compile property is asserted the same way: one miss per
    (statics, rounds, cadence, shapes, chunk policy), hits thereafter.
    ``sel_chunks`` is part of the key — it changes the lowered selection
    loop (bitwise-neutral, but a different program).  ``model_shard_key``
    (the mesh layout when the param_axes hook is armed, else None) is part
    of the key too: the sharded-model trace is a different program, as is
    the ``sharded`` one, whose lane axis is bound to the mesh's."""
    static = fl_static(fl)
    cache_key = ("pop", static, rounds, eval_every, meta, n_lanes,
                 pop.shapes(), int(sel_chunks), model_shard_key, sharded)
    runner = _RUNNER_CACHE.get(cache_key)
    if runner is None:
        RUNNER_STATS["misses"] += 1
        obs_trace.event("compile.runner_miss", engine="population",
                        model=static.model, rounds=rounds,
                        n_lanes=n_lanes, cache_size=len(_RUNNER_CACHE))
        with obs_trace.span("runner.build", engine="population",
                            model=static.model):
            single_run = _build_population_run(static, rounds, eval_every,
                                               meta, int(sel_chunks))
            # as in _get_runner: lanes bound to the mesh's ``lane`` axis
            runner = jax.jit(jax.vmap(
                single_run, in_axes=(0, None, 0),
                spmd_axis_name="lane" if sharded else None))
        _RUNNER_CACHE[cache_key] = runner
    else:
        RUNNER_STATS["hits"] += 1
    return runner


def run_fl_population(
    pop: Population,
    fl: FLConfig,
    params_grid: Optional[Sequence] = None,
    seeds: Sequence[int] = (0,),
    method: str = "proposed",
    rounds: Optional[int] = None,
    eval_every: int = 10,
    dataset: str = "unsw",
    hidden: int = 64,
    mesh_shape: Optional[tuple] = None,
    shard: bool = True,
    sel_chunks: Optional[int] = None,
    memory_budget_bytes: Optional[int] = None,
    model_replicated_max_bytes: Optional[int] = None,
) -> List[List[RunResult]]:
    """Population-scale front door: a hyper-parameter sweep over a
    100k+-client :class:`Population` as ONE compiled program.

    The lane semantics mirror :func:`run_fl_sweep` (cells × seeds lanes,
    results ``[cell][seed]``); the execution differs in three ways
    (ARCHITECTURE.md §Scale):

    * **client_cohort plan** — each round samples the cohort ON DEVICE
      (top-``ceil(k_eff)`` over the sharded utility scores) and gathers
      only those ``fl.k_max`` clients' membership rows and state to the
      compute lanes, so per-round compute and data traffic are O(k_max),
      independent of N (the sublinear-wall gate in
      benchmarks/bench_scale.py).
    * **2-D lane × client mesh** — :func:`repro.launch.mesh.make_scale_mesh`
      factorises the devices into (lane, client); lanes shard as in the
      sweep engine, and every per-client [N] array — the Population's
      membership table and the UtilityState/FaultState scan carries —
      shards over ``client`` (``models/sharding.py``).  ``mesh_shape``
      pins a layout; ``shard=False`` keeps everything replicated (the
      single-device program, used as the bitwise reference in
      tests/test_scale.py).
    * **auto-chunking policy** — when ``memory_budget_bytes`` is given,
      ``core/scale.auto_chunks`` sizes the selection chunk count so the
      [N]-shaped selection transients fit the per-device budget left
      after the resident population state + per-lane model replicas
      (DESIGN.md §7).  Chunked and unchunked selection are bitwise
      identical.
    * **model-sharding hook** — a detector whose ``param_bytes()``
      exceeds ``model_replicated_max_bytes`` (default
      ``core/scale.MODEL_REPLICATED_MAX_BYTES``) and declares
      ``ModelSpec.param_axes`` is traced under
      ``shardctx.sharding_ctx(RULES_MODEL_SCALE, mesh)``: its wide
      parameter axes tensor-parallel over the ``client`` mesh axis
      instead of replicating per lane.  History columns match the
      replicated run up to GSPMD reduction order
      (tests/test_models.py pins this on a 4-device mesh).

    ``fedl2p`` is rejected: its per-client personalisation pass is O(N)
    host work, which is exactly what this engine exists to avoid.
    """
    if method == "fedl2p":
        raise ValueError(
            "run_fl_population does not support fedl2p: its host-side "
            "personalisation fine-tunes every client (O(N) python loop) — "
            "use the dense engine at dense-federation scale")
    fl = fl_for_method(fl, method)
    if not fl.k_max or int(fl.k_max) <= 0:
        raise ValueError(
            "run_fl_population needs an explicit positive FLConfig.k_max "
            "(the static cohort size gathered per round)")
    rounds = int(rounds or fl.rounds)
    seeds = [int(s) for s in seeds]
    cells = _sweep_cells(fl, [fl] if params_grid is None else params_grid,
                         method, capability="cohort_capable")
    if not cells:
        return []
    n_lanes = len(cells) * len(seeds)

    with obs_trace.span("population.prepare", method=method, n_lanes=n_lanes,
                        n_cells=len(cells), rounds=rounds,
                        plans=",".join(sorted({c.plan for c in cells}))):
        meta = meta_for(pop, hidden=hidden)
        spec = get_model_spec(fl.model, meta)
        model_bytes = spec.param_bytes()

        if sel_chunks is None:
            sel_chunks = 1 if memory_budget_bytes is None else \
                scale_lib.auto_chunks(pop.n_clients, int(memory_budget_bytes),
                                      pop.members_per_client, n_lanes,
                                      model_bytes=model_bytes)

        mesh = make_scale_mesh(n_lanes, shape=mesh_shape) if shard else None
        n_padded = n_lanes
        if mesh is not None:
            lane_size = mesh.shape["lane"]
            n_padded = -(-n_lanes // lane_size) * lane_size

        # ModelSpec sharding hook: when the detector's replicated parameter
        # footprint exceeds the budget (core/scale.py) AND the spec declares
        # param_axes, trace the runner under the RULES_MODEL_SCALE context so
        # the spec's constrain_params calls tensor-parallel the model over the
        # mesh's client axis.  The decision is part of the runner-cache key —
        # sharded and replicated traces are different programs.
        model_ctx = contextlib.nullcontext()
        model_shard_key = None
        if (mesh is not None and mesh.shape["client"] > 1
                and spec.param_axes is not None
                and scale_lib.model_needs_sharding(model_bytes,
                                                   model_replicated_max_bytes)):
            model_ctx = shardctx.sharding_ctx(shard_lib.RULES_MODEL_SCALE,
                                              mesh)
            model_shard_key = tuple(sorted(mesh.shape.items()))

        t0 = time.time()
        runner = _get_population_runner(fl, rounds, eval_every, meta, n_padded,
                                        pop, sel_chunks,
                                        model_shard_key=model_shard_key,
                                        sharded=mesh is not None)
        keys = jax.vmap(jax.random.key)(
            jnp.asarray(np.tile(seeds, len(cells)), jnp.uint32))
        lanes = _params_lanes(cells, len(seeds))
        if n_padded > n_lanes:
            pad = n_padded - n_lanes
            keys = jnp.concatenate([keys, jnp.repeat(keys[-1:], pad, axis=0)])
            lanes = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.repeat(x[-1:], pad, axis=0)]),
                lanes)

        if mesh is not None:
            s_lane, _ = shard_lib.lane_shardings(mesh)
            keys = jax.device_put(keys, s_lane)
            lanes = jax.tree.map(lambda x: jax.device_put(x, s_lane), lanes)
            if pop.n_clients % mesh.shape["client"] == 0:
                pop = jax.device_put(
                    pop, shard_lib.population_shardings(mesh, pop))
            else:
                # uneven client axis: replicate rather than shard (correct but
                # unscaled — pad the population to a device multiple to shard)
                rep = NamedSharding(mesh, PartitionSpec())
                pop = jax.device_put(pop, jax.tree.map(lambda _: rep, pop))

    # the Pallas kernels, which XLA cannot partition, read this mesh to run
    # per device (kernels/ops.py::per_device)
    mesh_ctx = (jax.set_mesh(mesh) if mesh is not None
                else contextlib.nullcontext())
    with obs_trace.span("population.execute", n_lanes=n_lanes):
        with model_ctx, mesh_ctx:
            params_b, sim_b, trace_b = runner(keys, pop, lanes)
        jax.block_until_ready(sim_b)
    wall_per_lane = (time.time() - t0) / max(n_lanes, 1)

    eval_idx = _eval_rounds(rounds, eval_every)
    with obs_trace.span("population.readback", n_lanes=n_lanes):
        trace_np = {k: np.asarray(v) for k, v in trace_b.items()}
        sim_np = np.asarray(sim_b)
    out: List[List[RunResult]] = []
    with obs_trace.span("population.results", n_lanes=n_lanes):
        for ci, cell in enumerate(cells):
            scheduled = cell.dp_enabled and cell.dp_scheduled
            eps_cell = None if scheduled else accounted_epsilon(cell, rounds)
            row = []
            for si, seed in enumerate(seeds):
                lane = ci * len(seeds) + si
                history = {"round": [r + 1 for r in eval_idx]}
                for name in trace_np:
                    history[name] = [float(x) for x in trace_np[name][lane]]
                row.append(RunResult(
                    method=method, dataset=dataset, seed=seed,
                    accuracy=history["acc"][-1], auc=history["auc"][-1],
                    sim_time_s=float(sim_np[lane]), wall_time_s=wall_per_lane,
                    rounds=rounds,
                    eps_spent=history["eps"][-1] if scheduled else eps_cell,
                    history=history,
                ))
            out.append(row)
    return out


# ---------------------------------------------------------------------------
# Legacy engine: per-round Python loop (semantic oracle for the scan engine)
# ---------------------------------------------------------------------------


def run_fl_legacy(
    fed: FederatedData,
    fl: FLConfig,
    method: str = "proposed",
    seed: int = 0,
    rounds: Optional[int] = None,
    eval_every: int = 10,
    dataset: str = "unsw",
    hidden: int = 64,
) -> RunResult:
    """The original dispatch-per-round driver.  Kept (not deprecated) as the
    reference semantics: host-side NumPy batch sampling, one jit'd round
    step per iteration, eval pulled to host at every ``eval_every``.

    Scheduled-budget accounting (``dp_scheduled``) is a compiled-engine
    feature — the accountant/scheduler state rides the scan carry — so this
    loop rejects such configs instead of silently ignoring the budget."""
    fl = fl_for_method(fl, method)
    if fl.dp_enabled and fl.dp_scheduled:
        raise ValueError(
            "run_fl_legacy does not support dp_scheduled configs; use the "
            "compiled engine (run_fl / run_fl_batch / run_fl_sweep)")
    legacy_plan = plans_lib.get_plan(fl.plan)
    if legacy_plan.family != "client_parallel" or legacy_plan.code != 0.0:
        raise ValueError(
            f"run_fl_legacy implements only the synchronous client_parallel "
            f"plan; plan {fl.plan!r} needs the compiled engine "
            "(run_fl / run_fl_sweep)")
    rounds = rounds or fl.rounds
    rng = np.random.default_rng(seed)
    key = jax.random.key(seed)

    spec = get_model_spec(fl.model, meta_for(fed, hidden=hidden))
    params = spec.init(jax.random.fold_in(key, 0))
    sizes = fed.data_sizes()
    state = rounds_lib.init_round_state(
        params, fl, jax.random.fold_in(key, 1), n_clients=fed.n_clients,
        data_size=jnp.asarray(sizes / sizes.mean()),
        data_quality=jnp.asarray(fed.label_entropy()),
    )
    round_step = jax.jit(
        rounds_lib.make_parallel_round(spec.loss, fl, fed.n_clients)
    )

    tx, ty = jnp.asarray(fed.test_x), jnp.asarray(fed.test_y)
    history = {"round": [], "loss": [], "acc": [], "auc": [], "k": [],
               "fail": [], "cum_time": []}
    sim_time = 0.0
    t0 = time.time()
    for r in range(rounds):
        batches = jax.tree.map(
            jnp.asarray, round_batches(rng, fed, fl.local_epochs, fl.local_batch)
        )
        state, metrics = round_step(state, batches)
        sim_time += float(simulate_round_time(fl, state.util, metrics.sel_mask,
                                              metrics.failed,
                                              slow=metrics.slow))
        if (r + 1) % eval_every == 0 or r == rounds - 1:
            acc = float(spec.accuracy(state.params, tx, ty))
            proba = np.asarray(spec.predict_proba(state.params, tx)[:, 1])
            auc = auc_roc(proba, fed.test_y)
            history["round"].append(r + 1)
            history["loss"].append(float(metrics.global_loss))
            history["acc"].append(acc)
            history["auc"].append(auc)
            history["k"].append(float(metrics.k_effective))
            history["fail"].append(float(jnp.mean(metrics.failed)))
            history["cum_time"].append(sim_time)

    acc, auc = history["acc"][-1], history["auc"][-1]
    if method == "fedl2p":
        # personalisation pass (the point of FedL2P) + its simulated cost
        acc, auc = _personalize(state.params, fed, spec, seed=seed)
        sim_time *= 1.2
    eps = accounted_epsilon(fl, rounds)

    return RunResult(
        method=method, dataset=dataset, seed=seed,
        accuracy=acc, auc=auc,
        sim_time_s=sim_time, wall_time_s=time.time() - t0,
        rounds=rounds, eps_spent=eps, history=history,
    )
