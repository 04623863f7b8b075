"""The benchmark's own data generators, kept apart from the program's so
that no later change to the program can move the traffic.

A trimmed copy of ``src/repro/data/synthetic.py``: the UNSW-NB15-like flow
features, the ROAD-like raw CAN windows, a non-IID federation of them and a
population of lazily-sharded clients.  Everything is NumPy on the host and
a pure function of the seed.  One change from the program's generator: the
client shard sizes of a federation are a fixed multiset (drawn once from
``size_seed``) that the run's seed only permutes, so the padded device
stack has one shape for every seed and every run after the first finds
its programs in the compile cache.  The seed still draws the data, each
client's label mix and its covariate shift.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

UNSW_N_FEATURES = 42
UNSW_N_CLASSES = 10
UNSW_CLASS_PRIORS = np.array(
    [0.875, 0.024, 0.003, 0.002, 0.016, 0.044, 0.021, 0.010, 0.004, 0.001])
UNSW_CLASS_PRIORS = UNSW_CLASS_PRIORS / UNSW_CLASS_PRIORS.sum()


def seed32(seed: int) -> int:
    """A run seed folded into 32 bits, for the places that need one."""
    return int(seed) % (1 << 32)


def unsw_nb15_like(rng: np.random.Generator, n: int):
    """(X [n, 42] f32 standardised, y_bin [n] i32): per-class shifted
    features, lognormal volumes, squared rates, a shared latent."""
    y = rng.choice(UNSW_N_CLASSES, size=n, p=UNSW_CLASS_PRIORS)
    cls_shift = rng.normal(0.0, 1.2, (UNSW_N_CLASSES, UNSW_N_FEATURES))
    cls_mask = rng.random((UNSW_N_CLASSES, UNSW_N_FEATURES)) < 0.25
    cls_shift = cls_shift * cls_mask
    cls_shift[0] = 0.0
    x = cls_shift[y] + rng.normal(0.0, 1.0, (n, UNSW_N_FEATURES))
    x[:, :12] = np.exp(0.8 * x[:, :12])
    x[:, 12:22] = np.square(x[:, 12:22])
    latent = rng.normal(0.0, 1.0, (n, 4))
    mix = rng.normal(0.0, 0.4, (4, UNSW_N_FEATURES))
    x = x + latent @ mix
    x = (x - x.mean(0)) / (x.std(0) + 1e-9)
    return x.astype(np.float32), (y > 0).astype(np.int32)


def _roll_lastaxis(x: np.ndarray, shift: np.ndarray) -> np.ndarray:
    window = x.shape[-1]
    idx = (np.arange(window)[None, :] - shift[:, None]) % window
    return np.take_along_axis(x, idx, axis=-1)


def road_raw(rng: np.random.Generator, n: int, window: int = 64,
             n_signals: int = 6, attack_rate: float = 0.25,
             offset: float = 0.35):
    """(X [n, window·n_signals] f32, y [n] i32): raw CAN windows, flattened
    time-major and standardised.  Normal windows are AR(1) streams on a
    shared sinusoid driver; an attack window replays one signal's dynamics
    on another with a small offset (the ROAD correlated masquerade)."""
    y = (rng.random(n) < attack_rate).astype(np.int32)
    t = np.arange(window)
    freq = rng.uniform(0.5, 2.0, n)
    phase0 = rng.uniform(0, 6.28, n)
    driver = np.sin(2 * np.pi * t[None, :] / window * freq[:, None]
                    + phase0[:, None])
    phase = rng.uniform(0, 6.28, (n, n_signals))
    gain = rng.uniform(0.5, 1.5, (n, n_signals))
    ar = rng.uniform(0.7, 0.95, (n, n_signals))
    noise = rng.normal(0, 0.15, (n, n_signals, window))
    x = np.zeros((n, n_signals, window))
    for k in range(1, window):
        x[:, :, k] = ar * x[:, :, k - 1] + noise[:, :, k]
    shift_d = (phase * 3).astype(np.int64).reshape(-1)
    rolled = _roll_lastaxis(np.repeat(driver, n_signals, axis=0),
                            shift_d).reshape(n, n_signals, window)
    sig = gain[..., None] * rolled + x
    atk = np.flatnonzero(y)
    if atk.size:
        victim = rng.integers(0, n_signals, atk.size)
        src = (victim + rng.integers(1, n_signals, atk.size)) % n_signals
        shift = rng.integers(1, window // 4, atk.size)
        sig[atk, victim] = _roll_lastaxis(sig[atk, src], shift) + offset
    feats = sig.transpose(0, 2, 1).reshape(n, -1)
    feats = (feats - feats.mean(0)) / (feats.std(0) + 1e-9)
    return feats.astype(np.float32), y


def samples(rng: np.random.Generator, dataset: str, n: int,
            window: int = 64, n_signals: int = 6):
    """(X, y, feature_shape) of ``dataset`` ("unsw" or "road_raw")."""
    if dataset == "unsw":
        x, y = unsw_nb15_like(rng, n)
        return x, y, (x.shape[1],)
    if dataset == "road_raw":
        x, y = road_raw(rng, n, window=window, n_signals=n_signals)
        return x, y, (window, n_signals)
    raise ValueError(f"unknown dataset {dataset!r}")


def shard_sizes(size_seed: int, n_train: int, n_clients: int,
                alpha: float, min_per_client: int = 8) -> np.ndarray:
    """The fixed multiset of client shard sizes: Dirichlet(α) shares of the
    training rows, at least ``min_per_client`` each, summing to
    ``n_train``.  Drawn from ``size_seed`` only, so every run seed sees
    the same sizes (in its own order)."""
    rng = np.random.default_rng(size_seed)
    share = rng.dirichlet([alpha] * n_clients)
    spare = n_train - min_per_client * n_clients
    sizes = min_per_client + np.floor(share * spare).astype(np.int64)
    short = n_train - int(sizes.sum())
    sizes[np.argsort(-share)[:short]] += 1
    return sizes


@dataclass
class Federation:
    """Client shards (a list of arrays each) and the test set."""

    x: list
    y: list
    test_x: np.ndarray
    test_y: np.ndarray
    feature_shape: Tuple[int, ...]

    @property
    def n_features(self) -> int:
        return int(self.test_x.shape[1])


def federation(seed: int, dataset: str, n_samples: int, n_clients: int,
               alpha: float = 0.5, test_frac: float = 0.25,
               feature_shift: float = 0.15, size_seed: int = 0,
               window: int = 64, n_signals: int = 6) -> Federation:
    """A non-IID federation: each client draws a Gamma(α) attack
    propensity, takes that share of its (fixed-size) shard from the
    attack rows and the rest from the normal rows, without replacement
    across clients, and adds its own covariate shift."""
    rng = np.random.default_rng(seed)
    x, y, shape = samples(rng, dataset, n_samples, window, n_signals)
    n_test = int(n_samples * test_frac)
    perm = rng.permutation(n_samples)
    test_i, train_i = perm[:n_test], perm[n_test:]
    sizes = rng.permutation(shard_sizes(size_seed, len(train_i), n_clients,
                                        alpha))
    ytr = y[train_i]
    pos = rng.permutation(train_i[ytr == 1])
    neg = rng.permutation(train_i[ytr == 0])
    # attack rows per client: propensity-weighted, capped by the shard
    w = rng.gamma(alpha, 1.0, n_clients) * sizes
    want = np.minimum(np.floor(w / w.sum() * len(pos)).astype(np.int64),
                      sizes)
    left = len(pos) - int(want.sum())
    for ci in np.argsort(-w):
        if left <= 0:
            break
        add = min(left, int(sizes[ci] - want[ci]))
        want[ci] += add
        left -= add
    xs, ys = [], []
    p0 = p1 = 0
    for ci in range(n_clients):
        k1 = int(want[ci])
        k0 = int(sizes[ci]) - k1
        rows = np.concatenate([pos[p1:p1 + k1], neg[p0:p0 + k0]])
        p1 += k1
        p0 += k0
        rows = np.sort(rows)
        shift = rng.normal(0, feature_shift, x.shape[1]).astype(np.float32)
        xs.append(x[rows] + shift)
        ys.append(y[rows].copy())
    return Federation(xs, ys, x[test_i], y[test_i], shape)


@dataclass
class PopulationData:
    """A population over a shared pool: ``member_idx`` rows into the pool
    per client, a valid prefix ``member_size``, per-client size and
    label-entropy proxies; the covariate shift is drawn on the device from
    ``shift_seed`` and the client id."""

    pool_x: np.ndarray
    pool_y: np.ndarray
    member_idx: np.ndarray
    member_size: np.ndarray
    data_size: np.ndarray
    data_quality: np.ndarray
    test_x: np.ndarray
    test_y: np.ndarray
    shift_seed: int
    feature_shift: float


def population(seed: int, dataset: str, n_clients: int, pool_samples: int,
               members_per_client: int, alpha: float = 0.5,
               test_frac: float = 0.25, feature_shift: float = 0.15,
               chunk_clients: int = 16_384) -> PopulationData:
    """Clients' shards as rows into one pool: Beta(α, α) attack propensity
    per client, members drawn from the matching class bucket."""
    rng = np.random.default_rng(seed)
    x, y, _ = samples(rng, dataset, pool_samples)
    n_test = int(len(x) * test_frac)
    perm = rng.permutation(len(x))
    test_i, train_i = perm[:n_test], perm[n_test:]
    xtr, ytr = x[train_i], y[train_i]
    buckets = [np.flatnonzero(ytr == c) for c in (0, 1)]
    m = int(members_per_client)
    member_size = rng.integers(max(m // 2, 1), m + 1,
                               n_clients).astype(np.int32)
    member_idx = np.empty((n_clients, m), np.int32)
    quality = np.empty((n_clients,), np.float32)
    for lo in range(0, n_clients, chunk_clients):
        hi = min(lo + chunk_clients, n_clients)
        c = hi - lo
        p1 = rng.beta(alpha, alpha, c)
        n1 = rng.binomial(m, p1)
        cols = np.arange(m)[None, :]
        rows = np.where(
            cols < n1[:, None],
            buckets[1][rng.integers(0, len(buckets[1]), (c, m))],
            buckets[0][rng.integers(0, len(buckets[0]), (c, m))])
        order = rng.random((c, m)).argsort(axis=1)
        rows = np.take_along_axis(rows, order, axis=1)
        member_idx[lo:hi] = rows
        valid = cols < member_size[lo:hi][:, None]
        p = (ytr[rows] * valid).sum(1) / np.maximum(member_size[lo:hi], 1)
        p = np.clip(p, 1e-9, 1 - 1e-9)
        quality[lo:hi] = -(p * np.log(p) + (1 - p) * np.log(1 - p)) / np.log(2)
    sizes = member_size.astype(np.float32)
    return PopulationData(
        pool_x=xtr, pool_y=ytr.astype(np.int32), member_idx=member_idx,
        member_size=member_size, data_size=sizes / sizes.mean(),
        data_quality=quality, test_x=x[test_i],
        test_y=y[test_i].astype(np.int32),
        shift_seed=seed32(seed) ^ 0x5CA1E, feature_shift=float(feature_shift))


def label_entropy(ys, n_classes: int = 2) -> np.ndarray:
    """Per-client normalised label entropy (the data-quality proxy)."""
    out = []
    for yi in ys:
        p = np.bincount(yi, minlength=n_classes).astype(np.float64)
        p = p / max(p.sum(), 1)
        h = -(p[p > 0] * np.log(p[p > 0])).sum()
        out.append(h / np.log(n_classes))
    return np.asarray(out, np.float32)


def open_loop_arrivals(seed: int, rate: float, seconds: float,
                       pool_size: int):
    """A Poisson stream at ``rate`` windows/s over ``seconds``, conditioned
    on its count: exactly ``round(rate·seconds)`` arrivals at sorted
    uniform times (so every seed offers the same number of windows), each
    drawing a window of the pool uniformly.  Returns (due_s [n] f64,
    window_idx [n] i64)."""
    rng = np.random.default_rng(seed)
    n = int(round(rate * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    return due, rng.integers(0, pool_size, n)
