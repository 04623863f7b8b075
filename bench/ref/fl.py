"""Plain reference of one federated run's first eval block (Algorithm 1 of
the paper, as the configuration states it).

Written from the algorithm, in ``jax.numpy`` at a stated matmul precision
(``bench/ref/prec.py``), with nothing taken from the program.  What it
computes, per lane, for the first ``rounds`` rounds:

* availability Bernoulli(avail_prob); utility = α(0.3·perf_ema +
  0.25·quality·log1p(size/mean) + 5·coherence + 0.2·compute) −
  γ(comm + 0.5/compute) + 0.1·log1p(rounds since selected), plus a
  Gumbel exploration term; the top ceil(K) available clients train;
* i.i.d. failures at a uniform local step, with checkpoint recovery every
  2 steps (work after the last checkpoint is lost);
* each client: SGD over its local steps on batches drawn uniformly with
  replacement from its shard; the update is clipped to L2 norm ``clip``
  over the whole tree and gets N(0, σ²) per element,
  σ = clip·sqrt(2 ln(1.25/δ))/ε;
* FedAvg of the surviving selected updates weighted by normalised shard
  size; server SGD at ``server_lr``; utility EMAs (improvement, loss,
  coherence cos(Δ_i, Δ_agg), failures) and the adaptive-K controller
  (grow on a plateau of ``k_patience`` rounds, shrink while the loss
  falls tenfold faster than ``k_tol``);
* after the block: test accuracy, and the rank AUC of P(class 1) (ranks
  by a stable sort, no tie averaging).

The random draws are keyed as the configuration's run is: the lane key
splits into model init, the round state and the data stream, and each
round splits its key into availability, selection, failure and noise
keys.  The population form (``population_block``) selects the cohort by
top-k over the whole population and draws batches from the pool.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32_MIN = float(np.finfo(np.float32).min)


class Util(NamedTuple):
    perf: jnp.ndarray
    loss_ema: jnp.ndarray
    loss_var: jnp.ndarray
    coh: jnp.ndarray
    compute: jnp.ndarray
    comm: jnp.ndarray
    last: jnp.ndarray
    part: jnp.ndarray
    fail_ema: jnp.ndarray


def _init_util(key, n):
    k1, k2 = jax.random.split(key)
    z = jnp.zeros((n,), jnp.float32)
    return Util(perf=z, loss_ema=z + 2.0, loss_var=z + 1.0, coh=z,
                compute=jax.random.uniform(k1, (n,), minval=0.3, maxval=1.0),
                comm=jax.random.uniform(k2, (n,), minval=0.2, maxval=1.0),
                last=z, part=z, fail_ema=z)


def _utility(u: Util, size_norm, quality, fl):
    ds = size_norm / jnp.maximum(jnp.mean(size_norm), 1e-9)
    perf = 0.3 * u.perf
    qual = 0.25 * quality * jnp.log1p(ds) + 5.0 * u.coh
    stale = jnp.log1p(u.last) * 0.1
    cost = u.comm + (1.0 / jnp.maximum(u.compute, 0.1)) * 0.5
    return fl["alpha"] * (perf + qual + 0.2 * u.compute) - fl["gamma"] * cost + stale


def _update_util(u: Util, contrib, attempted, failed, pre, post, coh, e):
    m = contrib > 0
    imp = jnp.maximum(pre - post, -1.0)
    perf = jnp.where(m, (1 - e) * u.perf + e * imp, u.perf)
    loss_ema = jnp.where(m, (1 - e) * u.loss_ema + e * post, u.loss_ema)
    dev = (post - loss_ema) ** 2
    loss_var = jnp.where(m, (1 - e) * u.loss_var + e * dev, u.loss_var)
    cohe = jnp.where(m, (1 - e) * u.coh + e * coh, u.coh)
    fail_ema = jnp.where(attempted > 0, (1 - e) * u.fail_ema + e * failed,
                         u.fail_ema)
    return u._replace(perf=perf, loss_ema=loss_ema, loss_var=loss_var,
                      coh=cohe, last=jnp.where(m, 0.0, u.last + 1.0),
                      part=u.part + contrib, fail_ema=fail_ema)


def _update_k(k, best, plateau, loss, fl, k_max):
    tol, patience = fl["k_tol"], fl["k_patience"]
    improved = loss < best * (1.0 - tol)
    plateau = jnp.where(improved, 0.0, plateau + 1.0)
    grow = plateau >= patience
    k_new = jnp.where(grow, k + jnp.maximum(1.0, 0.25 * k), k)
    strong = jnp.isfinite(best) & (loss < best * (1.0 - 10.0 * tol))
    k_new = jnp.where(strong & ~grow, k_new - 1.0, k_new)
    k_new = jnp.clip(k_new, float(fl["k_min"]), float(k_max))
    return k_new, jnp.minimum(best, loss), jnp.where(grow, 0.0, plateau)


def _fail_steps(k_fail, p, n, steps, ckpt_every=2):
    fails = jax.random.bernoulli(jax.random.fold_in(k_fail, 1), p, (n,))
    at = jax.random.randint(jax.random.fold_in(k_fail, 2), (n,), 0, steps)
    fail_at = jnp.where(fails, at, steps)
    failed = fail_at < steps
    eff = jnp.where(failed, (fail_at // ckpt_every) * ckpt_every, steps)
    return eff, failed.astype(jnp.float32)


def _ce(logits, y):
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def _client_train(logits_fn, params, xs, ys, eff, lr, mode):
    """SGD over the local steps; steps at or past ``eff`` are lost work.
    Returns (Δ, first-step loss, last-step loss)."""
    def loss(p, x, y):
        return _ce(logits_fn(p, x, mode), y)

    def step(carry, b):
        p, s = carry
        val, g = jax.value_and_grad(loss)(p, b[0], b[1])
        new = jax.tree.map(lambda a, gg: a - lr * gg, p, g)
        p = jax.tree.map(lambda a, n: jnp.where(s < eff, n, a), p, new)
        return (p, s + 1), val

    (p, _), losses = jax.lax.scan(step, (params, jnp.zeros((), jnp.float32)),
                                  (xs, ys))
    return jax.tree.map(lambda a, b: a - b, p, params), losses[0], losses[-1]


def _privatize(delta, key, clip, sigma):
    leaves, tdef = jax.tree.flatten(delta)
    norm = jnp.sqrt(sum(jnp.sum(l * l) for l in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    keys = jax.random.split(key, len(leaves))
    out = [l * scale + sigma * jax.random.normal(k, l.shape, jnp.float32)
           for l, k in zip(leaves, keys)]
    return jax.tree.unflatten(tdef, out)


def _tree_dot(a, b):
    return sum(jnp.sum(x * y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _aggregate_and_score(params, deltas, contrib, weight, server_lr):
    w = contrib * weight
    denom = jnp.maximum(jnp.sum(w), 1e-9)
    agg = jax.tree.map(
        lambda d: jnp.sum(d * w.reshape((-1,) + (1,) * (d.ndim - 1)), 0) / denom,
        deltas)
    new = jax.tree.map(lambda p, a: p + server_lr * a, params, agg)
    agg_norm = jnp.sqrt(jnp.maximum(_tree_dot(agg, agg), 1e-18))

    def coh(d):
        nrm = jnp.sqrt(jnp.maximum(_tree_dot(d, d), 1e-18))
        return _tree_dot(d, agg) / (nrm * agg_norm)

    return new, jax.vmap(coh)(deltas) * contrib


def rank_auc(p1, y):
    """Rank AUC: ranks by a stable ascending sort, no tie averaging."""
    y = y.astype(jnp.float32)
    n_pos = jnp.sum(y)
    n_neg = y.shape[0] - n_pos
    order = jnp.argsort(p1, stable=True)
    ranks = jnp.zeros_like(p1).at[order].set(
        jnp.arange(1, p1.shape[0] + 1, dtype=jnp.float32))
    u = jnp.sum(ranks * y) - n_pos * (n_pos + 1.0) / 2.0
    return u / jnp.maximum(n_pos * n_neg, 1.0)


def _evaluate(logits_fn, params, tx, ty, mode, rows: int = 8192):
    """Accuracy and rank AUC over the test set, forward in row blocks."""
    n = tx.shape[0]
    pad = (-n) % rows
    xb = jnp.concatenate([tx, jnp.zeros((pad, tx.shape[1]), tx.dtype)])
    lg = jax.lax.map(lambda x: logits_fn(params, x, mode),
                     xb.reshape(-1, rows, tx.shape[1])).reshape(-1, 2)[:n]
    acc = jnp.mean((jnp.argmax(lg, -1) == ty).astype(jnp.float32))
    return acc, rank_auc(jax.nn.softmax(lg, -1)[:, 1], ty)


def sigma_for(eps, delta: float, clip):
    return clip * (math.sqrt(2.0 * math.log(1.25 / delta)) / eps)


def dense_block(model, fl: Dict, rounds: int, mode: str) -> Callable:
    """``run(key, eps, stack) -> (loss, acc, auc, k, fail)`` after
    ``rounds`` rounds of the dense federation (every client can be
    selected; only the selected clients' updates count).  ``stack`` holds
    the padded shards ``x [n, max_n, d]``, ``y``, ``sizes``, the normalised
    sizes ``weight``, the label-entropy ``quality`` and the test set."""
    steps, batch = fl["local_epochs"], fl["local_batch"]
    e = fl["utility_ema"]
    logits_fn = model.logits

    def run(key, eps, st):
        n = st["x"].shape[0]
        params = model.init(jax.random.fold_in(key, 0))
        k_state = jax.random.fold_in(key, 1)
        u = _init_util(k_state, n)
        carry = (params, u, jnp.float32(fl["clients_per_round"]),
                 jnp.float32(jnp.inf), jnp.float32(0.0), k_state,
                 jax.random.fold_in(key, 2))
        sigma = sigma_for(eps, fl["dp_delta"], fl["dp_clip"])

        def one_round(c, _):
            params, u, k, best, plateau, rng, data_key = c
            data_key, k_batch = jax.random.split(data_key)
            bkeys = jax.random.split(k_batch, n)
            idx = jax.vmap(lambda kk, sz: jax.random.randint(
                kk, (steps, batch), 0, sz))(bkeys, st["sizes"])
            xs = jax.vmap(lambda xi, ii: xi[ii])(st["x"], idx)
            ys = jax.vmap(lambda yi, ii: yi[ii])(st["y"], idx)
            rng, k_avail, k_sel, k_fail, k_dp = jax.random.split(rng, 5)
            avail = jax.random.bernoulli(k_avail, fl["avail_prob"], (n,))
            score = (_utility(u, st["weight"], st["quality"], fl)
                     + fl["explore_noise"] * jax.random.gumbel(k_sel, (n,)))
            order = jnp.argsort(-jnp.where(avail, score, F32_MIN), stable=True)
            rank = jnp.zeros((n,), jnp.float32).at[order].set(
                jnp.arange(n, dtype=jnp.float32))
            sel = ((rank < k) & avail).astype(jnp.float32)
            eff, failed = _fail_steps(k_fail, fl["failure_prob"], n, steps)
            deltas, pre, post = jax.vmap(
                lambda xx, yy, ee: _client_train(logits_fn, params, xx, yy, ee,
                                                 fl["local_lr"], mode))(xs, ys, eff)
            dkeys = jax.random.split(k_dp, n)
            deltas = jax.vmap(lambda d, kk: _privatize(
                d, kk, fl["dp_clip"], sigma))(deltas, dkeys)
            contrib = sel * (eff > 0)
            params, coh = _aggregate_and_score(params, deltas, contrib,
                                               st["weight"], fl["server_lr"])
            loss = jnp.sum(post * contrib) / jnp.maximum(jnp.sum(contrib), 1.0)
            u = _update_util(u, contrib, sel, failed, pre, post, coh, e)
            k_out = k
            k, best, plateau = _update_k(k, best, plateau, loss, fl, n)
            return ((params, u, k, best, plateau, rng, data_key),
                    (loss, k_out, jnp.mean(failed)))

        c, (losses, ks, fails) = jax.lax.scan(one_round, carry, None,
                                              length=rounds)
        acc, auc = _evaluate(model.score_logits, c[0], st["test_x"],
                             st["test_y"], mode)
        return losses[-1], acc, auc, ks[-1], fails[-1]

    return run


def population_block(model, fl: Dict, rounds: int, mode: str) -> Callable:
    """``run(key, eps, pop) -> (loss, acc, auc, k, fail)`` after ``rounds``
    rounds of a population federation: each round scores all N clients,
    takes the top ``k_max`` available (lower index first on ties) and
    trains the first ceil(K) of them on batches drawn from their pool
    rows plus their own covariate shift N(0, shift²) keyed by client id;
    per-client state is updated for the cohort only.  ``fail`` is the
    failure fraction over the whole population."""
    steps, batch = fl["local_epochs"], fl["local_batch"]
    k_max = int(fl["k_max"])
    e = fl["utility_ema"]
    logits_fn = model.logits

    def run(key, eps, pop):
        n = pop["member_idx"].shape[0]
        d = pop["pool_x"].shape[1]
        params = model.init(jax.random.fold_in(key, 0))
        k_state = jax.random.fold_in(key, 1)
        u = _init_util(k_state, n)
        carry = (params, u, jnp.float32(fl["clients_per_round"]),
                 jnp.float32(jnp.inf), jnp.float32(0.0), k_state,
                 jax.random.fold_in(key, 2))
        sigma = sigma_for(eps, fl["dp_delta"], fl["dp_clip"])
        shift_key = pop["shift_key"]

        def one_round(c, _):
            params, u, k, best, plateau, rng, data_key = c
            data_key, k_batch = jax.random.split(data_key)
            rng, k_avail, k_sel, k_fail, k_dp = jax.random.split(rng, 5)
            avail = jax.random.bernoulli(k_avail, fl["avail_prob"], (n,))
            k_eff = jnp.minimum(k, float(k_max))
            score = (_utility(u, pop["data_size"], pop["data_quality"], fl)
                     + fl["explore_noise"] * jax.random.gumbel(k_sel, (n,)))
            vals, idx = jax.lax.top_k(jnp.where(avail, score, F32_MIN), k_max)
            take = ((jnp.arange(k_max) < k_eff) & (vals > F32_MIN)).astype(jnp.float32)
            eff_all, failed_all = _fail_steps(k_fail, fl["failure_prob"], n, steps)
            eff, failed = eff_all[idx], failed_all[idx]
            skeys = jax.random.split(k_batch, k_max)

            def draw(kk, ci):
                j = jax.random.randint(kk, (steps, batch), 0,
                                       jnp.maximum(pop["member_size"][ci], 1))
                rows = pop["member_idx"][ci][j]
                shift = pop["feature_shift"] * jax.random.normal(
                    jax.random.fold_in(shift_key, ci), (d,))
                return pop["pool_x"][rows] + shift, pop["pool_y"][rows]

            xs, ys = jax.vmap(draw)(skeys, idx)
            deltas, pre, post = jax.vmap(
                lambda xx, yy, ee: _client_train(logits_fn, params, xx, yy, ee,
                                                 fl["local_lr"], mode))(xs, ys, eff)
            dkeys = jax.vmap(lambda ci: jax.random.fold_in(k_dp, ci))(idx)
            deltas = jax.vmap(lambda dd, kk: _privatize(
                dd, kk, fl["dp_clip"], sigma))(deltas, dkeys)
            contrib = take * (eff > 0)
            params, coh = _aggregate_and_score(params, deltas, contrib,
                                               pop["data_size"][idx],
                                               fl["server_lr"])
            loss = jnp.sum(post * contrib) / jnp.maximum(jnp.sum(contrib), 1.0)

            def full(v):
                return jnp.zeros((n,), jnp.float32).at[idx].add(v)

            u = _update_util(u, full(contrib), full(take), full(failed * take),
                             full(pre * contrib), full(post * contrib),
                             full(coh), e)
            k, best, plateau = _update_k(k, best, plateau, loss, fl, k_max)
            return ((params, u, k, best, plateau, rng, data_key),
                    (loss, k_eff, jnp.mean(failed_all)))

        c, (losses, ks, fails) = jax.lax.scan(one_round, carry, None,
                                              length=rounds)
        acc, auc = _evaluate(model.score_logits, c[0], pop["test_x"],
                             pop["test_y"], mode)
        return losses[-1], acc, auc, ks[-1], fails[-1]

    return run
