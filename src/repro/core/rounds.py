"""The FL round engine — Algorithm 1 as a lowered JAX step.

The execution plans are registered :class:`~repro.core.plans.RoundPlan`
contracts (DESIGN.md §4); this module provides their round-step builders:

* ``client_parallel`` (:func:`make_parallel_round`): clients live on the
  leading axis of every batch leaf (sharded over the ``data``(×``pod``)
  mesh axes).  Local training runs as a ``vmap`` over clients; aggregation
  is a masked weighted mean over the client axis (GSPMD turns it into the
  all-reduce).
* ``buffered_async`` / ``hierarchical`` — RUNTIME lanes of the SAME
  ``client_parallel`` program family: the parallel round step always
  lowers the FedBuff staleness-weighting and the two-tier edge
  aggregation, and the ``FLParams.plan_code`` lane selects them
  branch-free (0 sync flat | 1 async | 2 hierarchical), exactly like the
  ``fault_process`` code.  Code-0 lanes are bitwise the pre-registry
  engine: the selects are ``where``/``·1.0`` identities and no lane draws
  new RNG — async arrival order derives from the failure-scenario
  engine's emitted ``slow`` factors and the per-client compute
  capacities (``repro.fault.arrival_score``), never from fresh keys.
* ``client_serial`` (:func:`make_serial_round`): one client at a time with
  the WHOLE mesh (FSDP over ``data``); ``lax.scan`` over the K selected
  clients.  This is the only plan that fits ≥100B-parameter models.
* ``client_cohort`` (:func:`make_cohort_round`): the population-scale
  form — O(k_max) training over an on-device-sampled cohort.

Fault-tolerance semantics inside a lowered step (DESIGN.md §6): failure
times come from the pluggable failure-scenario engine (``repro/fault``) —
the runtime ``FLParams.fault_process`` code selects i.i.d. / Markov-bursty /
Weibull-lifetime / straggler processes branch-free, and the per-client
process state (:class:`~repro.fault.process.FaultState`) rides in
:class:`RoundState` so the engine's scan threads it.  Each failing client
loses the work after its last checkpoint — with checkpointing every ``c``
local steps a failure at step f keeps ``c·⌊f/c⌋`` steps; without
checkpointing the failed client contributes nothing.  Stragglers keep all
their work but stretch the simulated round time via the emitted per-client
``slow`` factors (``RoundMetrics.slow``).  Time overheads are accounted by
the cost model in ``core/fault.py`` at the driver level.  The serial plan
keeps the historical i.i.d. draw (same keys, via
``repro.fault.iid_fail_times``) — non-i.i.d. processes are a
``client_parallel`` feature; see DESIGN.md §6.

Differential privacy: each selected client's update Δ_i is clipped and
noised (``core/dp.py``) *before* aggregation — noise on updates, never on
utility scores, exactly as the paper specifies.

Static/runtime split (docs/ARCHITECTURE.md): the builders close over the
STATIC part of ``FLConfig`` only (shapes, plan, strategy name, booleans
that gate code structure).  Scalar hyper-parameters — learning rates, DP
budget, failure/availability probabilities, selection temperature,
adaptive-K thresholds — enter the built ``round_step`` as a runtime
:class:`FLParams` pytree argument (``round_step(state, batches, params)``),
so one compiled step serves an entire hyper-parameter grid.  Omitting
``params`` falls back to the values baked in the builder's config, which
keeps the original two-argument call sites working unchanged.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FLConfig, FLParams, fl_params
from repro.core import aggregation as agg
from repro.core import dp as dp_lib
from repro.core import selection as sel_lib
from repro.fault import process as fault_proc
from repro.optim.optimizers import make_server_optimizer, sgd


class RoundState(NamedTuple):
    """Carried across communication rounds."""

    params: Any
    server_opt_state: Any
    util: sel_lib.UtilityState
    kctl: sel_lib.KControllerState
    round_idx: jnp.ndarray
    rng: jnp.ndarray
    fault: fault_proc.FaultState


class RoundMetrics(NamedTuple):
    sel_mask: jnp.ndarray
    avail: jnp.ndarray
    failed: jnp.ndarray
    pre_loss: jnp.ndarray
    post_loss: jnp.ndarray
    global_loss: jnp.ndarray
    k_effective: jnp.ndarray
    update_norms: jnp.ndarray
    slow: jnp.ndarray      # [n] round-time stretch factors (straggler process)


def init_round_state(params, fl: FLConfig, key, n_clients=None, **util_kw) -> RoundState:
    n = n_clients or fl.n_clients
    server = make_server_optimizer(fl.server_opt, fl.server_lr)
    return RoundState(
        params=params,
        server_opt_state=server.init(params),
        util=sel_lib.init_utility_state(n, key=key, **util_kw),
        kctl=sel_lib.init_k_state(fl),
        round_idx=jnp.zeros((), jnp.int32),
        rng=key,
        fault=fault_proc.init_fault_state(n),
    )


def microbatched_value_and_grad(loss_fn, grad_accum: int):
    """Gradient accumulation: batch leaves [B, ...] are split into
    ``grad_accum`` microbatches scanned sequentially — the activation
    working set shrinks by grad_accum× (essential for the ≥100B configs)."""
    if grad_accum <= 1:
        return jax.value_and_grad(loss_fn)

    def vag(params, batch):
        mb = jax.tree.map(
            lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum) + x.shape[1:]),
            batch,
        )

        def step(carry, b):
            loss_acc, g_acc = carry
            loss, g = jax.value_and_grad(loss_fn)(params, b)
            g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), g_acc, g)
            return (loss_acc + loss, g_acc), None

        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, g), _ = jax.lax.scan(step, (jnp.zeros((), jnp.float32), zero_g), mb)
        scale = 1.0 / grad_accum
        return loss * scale, jax.tree.map(
            lambda gg, p: (gg * scale).astype(p.dtype), g, params
        )

    return vag


def _local_train_fn(loss_fn, fl: FLConfig, grad_accum: int = 1):
    """One client's local training: scan over local steps with step masking
    (effective_steps implements checkpoint-recovery truncation).

    ``lr`` is a runtime scalar (FLParams.local_lr) — a traced value is fine,
    so learning-rate sweeps share one compiled program."""
    vag = microbatched_value_and_grad(loss_fn, grad_accum)

    def local_train(global_params, step_batches, effective_steps, lr):
        opt = sgd(lr)

        def step(carry, xs):
            p, s = carry
            batch = xs
            loss, grads = vag(p, batch)
            new_p, _ = opt.update(grads, (), p)
            live = s < effective_steps
            # jnp.where keeps params in their storage dtype — no fp32
            # temporaries over the whole tree (2x param-size saving at 123B;
            # EXPERIMENTS.md §Perf A5)
            p = jax.tree.map(lambda a, b: jnp.where(live, b, a), p, new_p)
            return (p, s + 1), loss

        (p_final, _), losses = jax.lax.scan(
            step, (global_params, jnp.zeros((), jnp.float32)), step_batches
        )
        delta = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p_final, global_params,
        )
        return delta, losses[0], losses[-1]

    return local_train


def _effective_steps(fail_step, local_steps: int, ckpt_every: int, ft_enabled: bool):
    """Steps of work that survive a failure at ``fail_step``."""
    failed = fail_step < local_steps
    if not ft_enabled:
        return jnp.where(failed, 0, local_steps), failed
    c = max(int(ckpt_every), 1)
    kept = (fail_step // c) * c
    return jnp.where(failed, kept, local_steps), failed


# ---------------------------------------------------------------------------
# client_parallel plan
# ---------------------------------------------------------------------------


def _dp_sigma(fl: FLConfig, pr: FLParams):
    """Noise scale from runtime params (trace-safe; dp_mode stays static).

    Scheduled-budget runs (``fl.dp_scheduled``, STATIC) read σ straight
    from ``pr.dp_sigma``: the driver injects the scheduler's per-round
    value there (``pr._replace(dp_sigma=σ_t)``), so a traced, per-round σ
    flows into the clip+noise kernels with no recompile.
    """
    if fl.dp_mode == "paper" or fl.dp_scheduled:
        return pr.dp_sigma
    return dp_lib.gaussian_sigma_rt(pr.dp_epsilon, fl.dp_delta, pr.dp_clip)


def _gate_server_update(update_gate, new_params, new_server_state,
                        state: RoundState):
    """Budget-exhaustion masking (repro/privacy): with ``update_gate`` ≤ 0
    the aggregated release is withheld — global params AND server-optimizer
    state stay bitwise frozen, exactly as a deployment that halts at
    exhaustion.  ``update_gate`` is a traced 0/1 scalar, so exhaustion can
    flip mid-scan without recompiling; ``None`` (every pre-existing caller)
    compiles the identical ungated program."""
    if update_gate is None:
        return new_params, new_server_state
    live = update_gate > 0
    new_params = jax.tree.map(lambda n, o: jnp.where(live, n, o),
                              new_params, state.params)
    new_server_state = jax.tree.map(lambda n, o: jnp.where(live, n, o),
                                    new_server_state, state.server_opt_state)
    return new_params, new_server_state


def make_parallel_round(loss_fn: Callable, fl: FLConfig, n_clients: int,
                        ckpt_every_steps: int = 2,
                        dp_use_kernel: Optional[bool] = None,
                        grad_accum: int = 1, delta_constraint=None):
    """Build ``round_step(state, batches, params=None, update_gate=None)
    -> (state, metrics)``.

    batches: pytree whose leaves have leading [n_clients, local_steps, ...].
    ``params``: runtime :class:`FLParams`; ``None`` uses the builder config's
    values (back-compat).  ``update_gate``: optional traced 0/1 scalar —
    the privacy subsystem's budget-exhaustion mask (see
    :func:`_gate_server_update`).  Only the STATIC part of ``fl`` is closed
    over.
    ``delta_constraint``: optional fn applied to the stacked client deltas —
    steps.py uses it to pin the client axis onto the data mesh axes so GSPMD
    never materialises every client's weights on one shard.
    ``dp_use_kernel=None`` (default) auto-routes the per-client clip+noise:
    the fused Pallas kernel (``kernels/dp_clip_noise.py``) when the backend
    is TPU, the ``kernels/ref.py`` jnp fallback on CPU — ``core/dp.py``'s
    accountant stays the source of truth for ε either way.
    """
    strategy = sel_lib.get_strategy(fl.selection)
    local_train = _local_train_fn(loss_fn, fl, grad_accum)
    k_max = int(fl.k_max or n_clients)
    default_params = fl_params(fl)

    def round_step(state: RoundState, batches,
                   params: Optional[FLParams] = None,
                   update_gate=None) -> Tuple[RoundState, RoundMetrics]:
        pr = default_params if params is None else params
        server = make_server_optimizer(fl.server_opt, pr.server_lr)

        # Every op of the round sits under one phase scope, the same names
        # as the cohort plan's.  jax.named_scope markers are metadata-only
        # (profiler/HLO names); they never change the lowered math
        # (docs/DESIGN.md §8)
        with jax.named_scope("selection"):
            rng, k_avail, k_sel, k_fail, k_dp = jax.random.split(state.rng, 5)
            # ---- GetAvailableClients (Alg.1 line 3) ----
            avail = jax.random.bernoulli(k_avail, pr.avail_prob,
                                         (n_clients,)).astype(jnp.float32)
            # ---- ComputeUtility + SelectTopK (line 4) ----
            utility = sel_lib.compute_utility(state.util, fl,
                                              fault_w=pr.fault_util_w)
            k_eff = (state.kctl.k if fl.adaptive_k
                     else jnp.asarray(float(fl.clients_per_round),
                                      jnp.float32))
            sel_mask = strategy(k_sel, state.util, utility, avail, k_eff,
                                k_max, pr.explore_noise)

        # ---- failure injection + checkpoint-recovery truncation ----
        # process-emitted failure times (repro/fault): the runtime
        # fault_process code picks iid/markov/weibull/straggler lanes
        # branch-free; the iid lane reproduces the historical draw bitwise
        local_steps = jax.tree.leaves(batches)[0].shape[1]
        with jax.named_scope("fault"):
            fail_at, slow, new_fault = fault_proc.fault_step(
                state.fault, k_fail, pr, n_clients, local_steps)
            eff_steps, failed = _effective_steps(
                fail_at, local_steps, ckpt_every_steps, fl.fault_tolerance
            )

        # ---- local training, in parallel over clients (line 5) ----
        with jax.named_scope("local_train"):
            deltas, pre_loss, post_loss = jax.vmap(
                local_train, in_axes=(None, 0, 0, None)
            )(state.params, batches, eff_steps, pr.local_lr)
            if delta_constraint is not None:
                deltas = delta_constraint(deltas)

        # ---- DP: noise on updates, not on scores (lines 8-9) ----
        with jax.named_scope("dp_privatize"):
            if fl.dp_enabled:
                sigma = _dp_sigma(fl, pr)
                keys = jax.random.split(k_dp, n_clients)

                def privatize(d, k):
                    return dp_lib.privatize_update(
                        d, k, mode=fl.dp_mode, clip=pr.dp_clip, sigma=sigma,
                        use_kernel=dp_use_kernel,
                    )

                deltas, norms = jax.vmap(privatize)(deltas, keys)
            else:
                norms = jax.vmap(dp_lib.global_norm)(deltas)

        # ---- aggregation: async weights, edge tiers, server update ----
        with jax.named_scope("aggregate"):
            # drop clients whose surviving work is zero
            contrib_mask = sel_mask * (eff_steps > 0)

            # ---- buffered_async (plan_code 1): staleness-weighted arrivals
            # FedBuff semantics, eager-application form: every contributor's
            # update still lands this round, but discounted by how many
            # K-sized buffer flushes precede its arrival — staleness
            # s_i = floor(rank/K), weight (1+s)^-async_staleness_pow.
            # Arrival order comes from the failure-scenario engine (straggler
            # slow factors × compute capacity), NOT from new draws, so every
            # other lane's RNG stream is untouched.  On non-async lanes the
            # weight is exactly 1.0 and contrib·1.0 is bitwise contrib —
            # default lanes cannot move.
            with jax.named_scope("async_buffer"):
                arrive = fault_proc.arrival_score(slow, state.util.compute)
                arrive = jnp.where(contrib_mask > 0, arrive, jnp.inf)
                rank = jnp.argsort(jnp.argsort(arrive)).astype(jnp.float32)
                stale = jnp.floor(rank / jnp.maximum(pr.async_buffer, 1.0))
                stale_w = jnp.power(1.0 + stale, -pr.async_staleness_pow)
                agg_mask = contrib_mask * jnp.where(pr.plan_code == 1.0,
                                                    stale_w,
                                                    jnp.ones_like(stale_w))

            # ---- hierarchical (plan_code 2): edge FedAvg -> cloud mean ----
            # Client i reports to edge i % E (static shape,
            # E = hierarchy_edges); each edge computes the same weighted
            # FedAvg the flat plan does over its own group, and the cloud
            # takes the unweighted mean over LIVE edges (edges whose
            # contributor weight is nonzero).  Always lowered, selected away
            # by a where on non-hier lanes — the fault engine's branch-free
            # pattern.
            with jax.named_scope("hier_aggregate"):
                n_edges = max(int(fl.hierarchy_edges), 1)
                edge_id = jnp.arange(n_clients) % n_edges
                w_cli = (agg_mask * state.util.data_size).astype(jnp.float32)
                edge_w = jnp.zeros((n_edges,),
                                   jnp.float32).at[edge_id].add(w_cli)
                edge_live = (edge_w > 0).astype(jnp.float32)
                n_live = jnp.maximum(jnp.sum(edge_live), 1.0)
                is_hier = pr.plan_code == 2.0

                def _hier_agg(d):
                    df = d.astype(jnp.float32)
                    wb = w_cli.reshape((-1,) + (1,) * (df.ndim - 1))
                    esum = jnp.zeros((n_edges,) + df.shape[1:],
                                     jnp.float32).at[edge_id].add(df * wb)
                    tail = (1,) * (df.ndim - 1)
                    edelta = esum / jnp.maximum(edge_w, 1e-9).reshape(
                        (-1,) + tail)
                    live = edge_live.reshape((-1,) + tail)
                    return jnp.sum(edelta * live, axis=0) / n_live

            # ---- aggregation + server update (line 18) ----
            agg_delta = agg.aggregate_stacked(deltas, agg_mask,
                                              state.util.data_size)
            agg_delta = jax.tree.map(
                lambda flat, d: jnp.where(is_hier, _hier_agg(d), flat),
                agg_delta, deltas)
            new_params, new_server_state = agg.apply_server_update(
                server, state.params, state.server_opt_state, agg_delta
            )
            new_params, new_server_state = _gate_server_update(
                update_gate, new_params, new_server_state, state)

            # ---- update-coherence (data-quality observable): cos(Δ_i, Δ_agg)
            def _dot(a, b):
                return sum(
                    jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))
                    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

            agg_norm = jnp.sqrt(jnp.maximum(_dot(agg_delta, agg_delta), 1e-18))

            def _coh(delta_i):
                num = sum(
                    jnp.sum(d.astype(jnp.float32) * g.astype(jnp.float32))
                    for d, g in zip(jax.tree.leaves(delta_i),
                                    jax.tree.leaves(agg_delta))
                )
                nrm = jnp.sqrt(jnp.maximum(_dot(delta_i, delta_i), 1e-18))
                return num / (nrm * agg_norm)

            if fl.coherence_scoring:
                coherence = jax.vmap(_coh)(deltas) * contrib_mask
            else:
                coherence = None

        # ---- per-client carries: utility EMAs, adaptive K ----
        with jax.named_scope("carry_update"):
            sel_denom = jnp.maximum(jnp.sum(contrib_mask), 1.0)
            global_loss = jnp.sum(post_loss * contrib_mask) / sel_denom
            failed_f = failed.astype(jnp.float32)
            util = sel_lib.update_utility_state(
                state.util, contrib_mask, pre_loss, post_loss, fl,
                coherence=coherence, attempted=sel_mask, failed=failed_f)
            kctl = sel_lib.update_k(state.kctl, global_loss, fl,
                                    tol=pr.k_tol, patience=pr.k_patience)

        new_state = RoundState(new_params, new_server_state, util, kctl,
                               state.round_idx + 1, rng, new_fault)
        metrics = RoundMetrics(sel_mask, avail, failed_f,
                               pre_loss, post_loss, global_loss, k_eff, norms,
                               slow)
        return new_state, metrics

    return round_step


# ---------------------------------------------------------------------------
# client_cohort plan (population scale: train the gathered cohort only)
# ---------------------------------------------------------------------------


class CohortMetrics(NamedTuple):
    """Round metrics in cohort form: ``[k_max]``-shaped where
    :class:`RoundMetrics` was ``[n_clients]``-shaped.  At 10^5+ clients
    the dense form would emit O(N) per round; the driver only ever needs
    the cohort rows plus population scalars."""

    cohort_idx: jnp.ndarray    # [k_max] i32 selected client ids
    take: jnp.ndarray          # [k_max] f32 live-slot mask (rank < k_eff)
    failed: jnp.ndarray        # [k_max] f32 failure indicator (cohort)
    slow: jnp.ndarray          # [k_max] f32 straggler stretch (cohort)
    pre_loss: jnp.ndarray      # [k_max]
    post_loss: jnp.ndarray     # [k_max]
    global_loss: jnp.ndarray
    k_effective: jnp.ndarray
    update_norms: jnp.ndarray  # [k_max]
    fail_frac: jnp.ndarray     # population-wide failure fraction


def make_cohort_round(loss_fn: Callable, fl: FLConfig, n_clients: int,
                      sample_fn: Callable,
                      ckpt_every_steps: int = 2,
                      dp_use_kernel: Optional[bool] = None,
                      grad_accum: int = 1,
                      sel_chunks: int = 1):
    """Build the population-scale round:
    ``round_step(state, pop, data_key, params=None, update_gate=None) ->
    (state, CohortMetrics)``.

    Same Algorithm-1 semantics as :func:`make_parallel_round`, restructured
    so per-round COMPUTE is O(k_max) while only O(N) *vector* work touches
    the full population (DESIGN.md §7, ARCHITECTURE.md §Scale):

    1. availability, utility scores and the failure processes evaluate as
       [N] vector ops (shardable over the ``client`` mesh axis);
    2. :func:`~repro.core.selection.cohort_topk` picks the ceil(k_eff)
       cohort ON DEVICE from the (sharded) scores — ``sel_chunks`` is the
       auto-chunking policy's knob (``core/scale.py``), bitwise-neutral;
    3. ``sample_fn(key, pop, cohort_idx)`` gathers ONLY the cohort's data
       (the driver closes it over
       :func:`repro.data.synthetic.sample_cohort_batches`);
    4. local training / DP / aggregation run over the k_max cohort slots;
    5. per-client carries (utility EMAs, ``fail_ema``, FaultState) update
       via scatters back into the [N] state — the same
       ``update_utility_state`` rule the dense plans use.

    ``fl.k_max`` must be a positive static (it sizes the cohort): the
    dense plans' ``0 -> n_clients`` default would defeat the point at
    population scale, so it is rejected loudly.  DP noise keys are
    ``fold_in(k_dp, client_id)`` — a stable per-client stream independent
    of cohort composition.
    """
    score_fn = sel_lib.get_score_fn(fl.selection)
    local_train = _local_train_fn(loss_fn, fl, grad_accum)
    if not fl.k_max or int(fl.k_max) <= 0:
        raise ValueError(
            "the client_cohort plan needs an explicit positive FLConfig."
            "k_max (it is the static cohort size gathered to the compute "
            "lanes); the dense default 0 -> n_clients would train the "
            "whole population")
    k_max = int(fl.k_max)
    local_steps = int(fl.local_epochs)
    default_params = fl_params(fl)

    def round_step(state: RoundState, pop, data_key,
                   params: Optional[FLParams] = None,
                   update_gate=None) -> Tuple[RoundState, CohortMetrics]:
        pr = default_params if params is None else params
        server = make_server_optimizer(fl.server_opt, pr.server_lr)

        # Phase scopes as in make_parallel_round, in round order; the O(N)
        # population vector work is selection, fault and carry_update.
        with jax.named_scope("selection"):
            rng, k_avail, k_sel, k_fail, k_dp = jax.random.split(state.rng, 5)
            avail = jax.random.bernoulli(k_avail, pr.avail_prob,
                                         (n_clients,)).astype(jnp.float32)
            utility = sel_lib.compute_utility(state.util, fl,
                                              fault_w=pr.fault_util_w)
            k_eff = (state.kctl.k if fl.adaptive_k
                     else jnp.asarray(float(fl.clients_per_round),
                                      jnp.float32))
            k_eff = jnp.minimum(k_eff, float(k_max))
            scores = score_fn(k_sel, state.util, utility, avail,
                              pr.explore_noise)
            idx, take = sel_lib.cohort_topk(scores, avail, k_eff, k_max,
                                            chunks=sel_chunks)

        with jax.named_scope("fault"):
            fail_at_full, slow_full, new_fault = fault_proc.fault_step(
                state.fault, k_fail, pr, n_clients, local_steps)
            fail_frac = jnp.mean((fail_at_full < local_steps)
                                 .astype(jnp.float32))
            fail_at, slow = fault_proc.gather_cohort(fail_at_full, slow_full,
                                                     idx)
            eff_steps, failed = _effective_steps(
                fail_at, local_steps, ckpt_every_steps, fl.fault_tolerance)

        # ---- cohort gather + O(k_max) training phase ----
        with jax.named_scope("batch_sample"):
            batches = sample_fn(data_key, pop, idx)

        with jax.named_scope("local_train"):
            deltas, pre_loss, post_loss = jax.vmap(
                local_train, in_axes=(None, 0, 0, None)
            )(state.params, batches, eff_steps, pr.local_lr)

        with jax.named_scope("dp_privatize"):
            if fl.dp_enabled:
                sigma = _dp_sigma(fl, pr)
                keys = jax.vmap(lambda c: jax.random.fold_in(k_dp, c))(idx)

                def privatize(d, k):
                    return dp_lib.privatize_update(
                        d, k, mode=fl.dp_mode, clip=pr.dp_clip, sigma=sigma,
                        use_kernel=dp_use_kernel,
                    )

                deltas, norms = jax.vmap(privatize)(deltas, keys)
            else:
                norms = jax.vmap(dp_lib.global_norm)(deltas)

        with jax.named_scope("aggregate"):
            contrib = take * (eff_steps > 0)
            agg_delta = agg.aggregate_stacked(deltas, contrib,
                                              state.util.data_size[idx])
            new_params, new_server_state = agg.apply_server_update(
                server, state.params, state.server_opt_state, agg_delta)
            new_params, new_server_state = _gate_server_update(
                update_gate, new_params, new_server_state, state)

            if fl.coherence_scoring:
                def _dot(a, b):
                    return sum(
                        jnp.sum(x.astype(jnp.float32) * y.astype(jnp.float32))
                        for x, y in zip(jax.tree.leaves(a),
                                        jax.tree.leaves(b)))

                agg_norm = jnp.sqrt(jnp.maximum(_dot(agg_delta, agg_delta),
                                                1e-18))

                def _coh(delta_i):
                    num = sum(
                        jnp.sum(d.astype(jnp.float32) * g.astype(jnp.float32))
                        for d, g in zip(jax.tree.leaves(delta_i),
                                        jax.tree.leaves(agg_delta)))
                    nrm = jnp.sqrt(jnp.maximum(_dot(delta_i, delta_i), 1e-18))
                    return num / (nrm * agg_norm)

                coherence_c = jax.vmap(_coh)(deltas) * contrib
            else:
                coherence_c = None

        # ---- scatter back into the [N] carries ----
        with jax.named_scope("carry_update"):
            def scatter(vals_c):
                return jnp.zeros((n_clients,), jnp.float32).at[idx].add(vals_c)

            sel_full = scatter(take)
            contrib_full = scatter(contrib)
            failed_f = failed.astype(jnp.float32)
            sel_denom = jnp.maximum(jnp.sum(contrib), 1.0)
            global_loss = jnp.sum(post_loss * contrib) / sel_denom
            util = sel_lib.update_utility_state(
                state.util, contrib_full,
                scatter(pre_loss * contrib), scatter(post_loss * contrib), fl,
                coherence=(None if coherence_c is None
                           else scatter(coherence_c)),
                attempted=sel_full, failed=scatter(failed_f * take))
            kctl = sel_lib.update_k(state.kctl, global_loss, fl,
                                    tol=pr.k_tol, patience=pr.k_patience)

        new_state = RoundState(new_params, new_server_state, util, kctl,
                               state.round_idx + 1, rng, new_fault)
        metrics = CohortMetrics(
            cohort_idx=idx, take=take, failed=failed_f * take,
            slow=slow, pre_loss=pre_loss, post_loss=post_loss,
            global_loss=global_loss, k_effective=k_eff, update_norms=norms,
            fail_frac=fail_frac)
        return new_state, metrics

    return round_step


# ---------------------------------------------------------------------------
# client_serial plan (for >=8B models; whole mesh per client)
# ---------------------------------------------------------------------------


def make_serial_round(loss_fn: Callable, fl: FLConfig, n_clients: int,
                      ckpt_every_steps: int = 2,
                      dp_use_kernel: Optional[bool] = None, grad_accum: int = 1,
                      delta_dtype=None):
    """Build ``round_step(state, batches, params=None) -> (state, metrics)``.

    batches leaves: [K, local_steps, ...] — data for the K client slots that
    the host-side driver filled with the selected clients' shards (the
    in-step selection produces the slot→client mapping used for weighting).
    K = fl.serial_clients_in_step is static.  ``ckpt_every_steps`` is the
    same checkpoint interval the parallel plan takes (it used to be
    hardcoded to 2 here, so a configured interval silently only applied to
    the parallel plan).  ``params``/``update_gate``: runtime
    :class:`FLParams` and the budget-exhaustion mask, as in
    :func:`make_parallel_round`.
    """
    strategy = sel_lib.get_strategy(fl.selection)
    local_train = _local_train_fn(loss_fn, fl, grad_accum)
    K = fl.serial_clients_in_step
    k_max = int(fl.k_max or n_clients)
    default_params = fl_params(fl)

    def round_step(state: RoundState, batches,
                   params: Optional[FLParams] = None,
                   update_gate=None) -> Tuple[RoundState, RoundMetrics]:
        pr = default_params if params is None else params
        server = make_server_optimizer(fl.server_opt, pr.server_lr)
        sigma = _dp_sigma(fl, pr) if fl.dp_enabled else 0.0
        rng, k_avail, k_sel, k_fail, k_dp = jax.random.split(state.rng, 5)
        avail = jax.random.bernoulli(k_avail, pr.avail_prob,
                                     (n_clients,)).astype(jnp.float32)
        utility = sel_lib.compute_utility(state.util, fl,
                                          fault_w=pr.fault_util_w)
        k_eff = jnp.minimum(
            state.kctl.k if fl.adaptive_k else float(fl.clients_per_round), float(K)
        )
        sel_mask = strategy(k_sel, state.util, utility, avail, k_eff,
                            min(K, k_max), pr.explore_noise)
        # slot i <- i-th selected client (host driver feeds matching data)
        _, sel_idx = jax.lax.top_k(sel_mask + utility * 1e-6, K)
        slot_live = (jnp.arange(K) < k_eff).astype(jnp.float32)

        # the serial plan keeps the historical i.i.d. draw (per SLOT, so a
        # per-client process state cannot follow the slot→client remapping
        # across rounds); non-iid fault processes are a client_parallel
        # feature — DESIGN.md §6
        local_steps = jax.tree.leaves(batches)[0].shape[1]
        fail_at = fault_proc.iid_fail_times(
            k_fail, jax.random.fold_in(k_fail, 1), pr.failure_prob, K,
            local_steps)
        eff_steps, failed = _effective_steps(fail_at, local_steps,
                                             ckpt_every_steps,
                                             fl.fault_tolerance)

        def per_client(carry, xs):
            acc, pre_l, post_l, norms, slot = carry
            client_batches, e_steps, live = xs
            delta, pre, post = local_train(state.params, client_batches,
                                           e_steps, pr.local_lr)
            if fl.dp_enabled:
                delta, norm = dp_lib.privatize_update(
                    delta, jax.random.fold_in(k_dp, slot),
                    mode=fl.dp_mode, clip=pr.dp_clip, sigma=sigma,
                    use_kernel=dp_use_kernel,
                )
            else:
                norm = dp_lib.global_norm(delta)
            m = live * (e_steps > 0)
            acc = agg.stream_accumulate(acc, delta, m, 1.0)
            return (
                acc,
                pre_l.at[slot].set(pre),
                post_l.at[slot].set(post),
                norms.at[slot].set(norm),
                slot + 1,
            ), None

        acc0 = agg.stream_init(state.params, delta_dtype or jnp.float32)
        zK = jnp.zeros((K,), jnp.float32)
        (acc, pre_loss, post_loss, norms, _), _ = jax.lax.scan(
            per_client,
            (acc0, zK, zK, zK, jnp.zeros((), jnp.int32)),
            (batches, eff_steps, slot_live),
        )
        agg_delta = agg.stream_finalize(acc)
        new_params, new_server_state = agg.apply_server_update(
            server, state.params, state.server_opt_state, agg_delta
        )
        new_params, new_server_state = _gate_server_update(
            update_gate, new_params, new_server_state, state)

        contrib = slot_live * (eff_steps > 0)
        denom = jnp.maximum(jnp.sum(contrib), 1.0)
        global_loss = jnp.sum(post_loss * contrib) / denom
        # scatter slot losses back to the selected clients' utility entries
        full_mask = jnp.zeros((n_clients,), jnp.float32).at[sel_idx].add(contrib)
        full_pre = jnp.zeros((n_clients,), jnp.float32).at[sel_idx].add(pre_loss * contrib)
        full_post = jnp.zeros((n_clients,), jnp.float32).at[sel_idx].add(post_loss * contrib)
        util = sel_lib.update_utility_state(state.util, full_mask, full_pre, full_post, fl)
        kctl = sel_lib.update_k(state.kctl, global_loss, fl,
                                tol=pr.k_tol, patience=pr.k_patience)

        new_state = RoundState(new_params, new_server_state, util, kctl,
                               state.round_idx + 1, rng, state.fault)
        metrics = RoundMetrics(full_mask, avail, failed.astype(jnp.float32),
                               full_pre, full_post, global_loss, k_eff, norms,
                               jnp.ones((n_clients,), jnp.float32))
        return new_state, metrics

    return round_step
