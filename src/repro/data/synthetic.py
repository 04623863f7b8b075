"""Synthetic stand-ins for the paper's datasets (offline container).

* ``unsw_nb15_like`` — mirrors the UNSW-NB15 schema: 42 numeric flow
  features (durations, byte/packet counts, rates, TTLs, window sizes, ...)
  drawn from per-class lognormal/gamma/normal mixtures; 10 classes (normal +
  9 attack categories: fuzzers, analysis, backdoor, dos, exploits, generic,
  recon, shellcode, worms) with the published heavy class imbalance
  (~87.5% normal traffic).
* ``road_like`` — CAN-bus windows mimicking the ROAD *correlated masquerade*
  attack: per-ID correlated signal streams; an attack replays one signal's
  dynamics on another ID with a small offset — statistically stealthy, which
  is exactly the ROAD difficulty.

Non-IID federation: Dirichlet(α) label skew + per-client feature shift, as
assumed by the paper ("non-IID data distribution across clients").
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

UNSW_N_FEATURES = 42
UNSW_N_CLASSES = 10
UNSW_CLASS_PRIORS = np.array(
    [0.875, 0.024, 0.003, 0.002, 0.016, 0.044, 0.021, 0.010, 0.004, 0.001]
)
UNSW_CLASS_PRIORS = UNSW_CLASS_PRIORS / UNSW_CLASS_PRIORS.sum()


def unsw_nb15_like(rng: np.random.Generator, n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Returns (X [n,42] float32 standardised, y_cat [n], y_bin [n])."""
    y = rng.choice(UNSW_N_CLASSES, size=n, p=UNSW_CLASS_PRIORS)
    X = np.empty((n, UNSW_N_FEATURES), np.float64)

    # class-conditional generative structure: each class shifts a subset of
    # features (e.g. DoS inflates packet rates; recon touches many ports)
    base_mu = rng.normal(0.0, 1.0, (UNSW_N_CLASSES, UNSW_N_FEATURES)) * 0.0
    cls_shift = rng.normal(0.0, 1.2, (UNSW_N_CLASSES, UNSW_N_FEATURES))
    cls_mask = rng.random((UNSW_N_CLASSES, UNSW_N_FEATURES)) < 0.25
    cls_shift = cls_shift * cls_mask
    cls_shift[0] = 0.0  # normal traffic is the reference

    # heavy-tailed "volume" features (bytes, packets, duration): lognormal
    heavy = np.zeros(UNSW_N_FEATURES, bool)
    heavy[:12] = True
    # rate-like features: gamma
    ratef = np.zeros(UNSW_N_FEATURES, bool)
    ratef[12:22] = True

    mu = base_mu[y] + cls_shift[y]
    z = rng.normal(0.0, 1.0, (n, UNSW_N_FEATURES))
    X = mu + z
    X[:, heavy] = np.exp(0.8 * X[:, heavy])  # lognormal tails
    X[:, ratef] = np.square(X[:, ratef])  # chi2-ish rates

    # correlated flow structure (shared latent per sample)
    latent = rng.normal(0.0, 1.0, (n, 4))
    mix = rng.normal(0.0, 0.4, (4, UNSW_N_FEATURES))
    X = X + latent @ mix

    # standardise
    X = (X - X.mean(0)) / (X.std(0) + 1e-9)
    return X.astype(np.float32), y.astype(np.int32), (y > 0).astype(np.int32)


def _corr_lastaxis(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Pearson correlation along the last axis (batched ``np.corrcoef``);
    0 where either side is (near-)constant."""
    ac = a - a.mean(-1, keepdims=True)
    bc = b - b.mean(-1, keepdims=True)
    sa = np.sqrt((ac * ac).mean(-1))
    sb = np.sqrt((bc * bc).mean(-1))
    denom = sa * sb
    safe = denom > 1e-18
    num = (ac * bc).mean(-1)
    return np.where(safe, num / np.where(safe, denom, 1.0), 0.0)


def _roll_lastaxis(x: np.ndarray, shift: np.ndarray) -> np.ndarray:
    """``np.roll`` along the last axis with a per-row shift (gather)."""
    window = x.shape[-1]
    idx = (np.arange(window)[None, :] - shift[:, None]) % window
    return np.take_along_axis(x, idx, axis=-1)


def road_like(
    rng: np.random.Generator,
    n: int,
    window: int = 64,
    n_signals: int = 6,
    attack_rate: float = 0.25,
    offset: float = 0.35,
    raw: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Correlated-masquerade CAN windows.

    Normal windows: n_signals AR(1) streams with a shared low-frequency
    driver (vehicle state).  Attack: one signal is replaced by a *replay* of
    another signal's dynamics plus a small constant offset — the masquerade.
    Features: per-signal (mean, std, mean |Δ|, lag-1 autocorr, corr to
    signal 0) -> 5·n_signals features.
    Returns (X, y, y) — binary labels only (matches our ROAD use).

    ``raw=True`` skips the hand-engineered statistics and returns the
    standardised window matrix itself, flattened time-major to
    ``[n, window·n_signals]`` (reshape with ``feature_shape = (window,
    n_signals)`` recovers ``[window, n_signals]``) — the input the
    window-native detectors in ``models/detectors.py`` consume.  The RNG
    draw order is identical to the feature path, so raw and feature
    datasets of one seed describe the same windows.

    Fully vectorised across windows/signals (the per-window Python loop made
    this the hot spot of ``benchmarks/run.py``); only the AR(1) recursion
    iterates, over the ``window`` axis.  ``_road_like_loop`` keeps the
    original per-window implementation as the statistical oracle
    (tests/test_synthetic_road.py) — the two draw the RNG in different
    orders, so they match in distribution, not sample-for-sample.
    """
    y = (rng.random(n) < attack_rate).astype(np.int32)
    t = np.arange(window)

    # shared low-frequency driver per window: sin(2π t/window · f + φ0)
    freq = rng.uniform(0.5, 2.0, n)
    phase0 = rng.uniform(0, 6.28, n)
    driver = np.sin(2 * np.pi * t[None, :] / window * freq[:, None]
                    + phase0[:, None])                       # [n, window]

    phase = rng.uniform(0, 6.28, (n, n_signals))
    gain = rng.uniform(0.5, 1.5, (n, n_signals))
    ar = rng.uniform(0.7, 0.95, (n, n_signals))
    noise = rng.normal(0, 0.15, (n, n_signals, window))

    # AR(1): x_k = ar·x_{k-1} + noise_k — sequential in k only
    x = np.zeros((n, n_signals, window))
    for k in range(1, window):
        x[:, :, k] = ar * x[:, :, k - 1] + noise[:, :, k]

    shift_d = (phase * 3).astype(np.int64).reshape(-1)
    rolled = _roll_lastaxis(
        np.repeat(driver, n_signals, axis=0), shift_d
    ).reshape(n, n_signals, window)
    sig = gain[..., None] * rolled + x

    # masquerade: victim signal <- replayed source + offset, attack rows only
    atk = np.flatnonzero(y)
    if atk.size:
        victim = rng.integers(0, n_signals, atk.size)
        # uniform ordered pair without replacement: src = victim + U[1, S)
        src = (victim + rng.integers(1, n_signals, atk.size)) % n_signals
        shift = rng.integers(1, window // 4, atk.size)
        sig[atk, victim] = _roll_lastaxis(sig[atk, src], shift) + offset

    if raw:
        feats = sig.transpose(0, 2, 1).reshape(n, -1)  # time-major flatten
        feats = (feats - feats.mean(0)) / (feats.std(0) + 1e-9)
        return feats.astype(np.float32), y, y

    # per-signal features: mean, std, mean |Δ|, lag-1 autocorr, corr to sig 0
    mean = sig.mean(-1)
    std = sig.std(-1)
    dxm = np.abs(np.diff(sig, axis=-1)).mean(-1)
    live = std > 1e-9
    acorr = np.where(live, _corr_lastaxis(sig[..., :-1], sig[..., 1:]), 0.0)
    c0 = np.where(live, _corr_lastaxis(sig, sig[:, :1]), 0.0)
    c0[:, 0] = 1.0
    feats = np.stack([mean, std, dxm, acorr, c0], axis=-1).reshape(n, -1)
    feats = (feats - feats.mean(0)) / (feats.std(0) + 1e-9)
    return feats.astype(np.float32), y, y


def _road_like_loop(
    rng: np.random.Generator,
    n: int,
    window: int = 64,
    n_signals: int = 6,
    attack_rate: float = 0.25,
    offset: float = 0.35,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Original per-window loop — the oracle :func:`road_like` is tested
    against for statistical equivalence (kept small-n only; it is the slow
    path the vectorisation replaced)."""
    y = (rng.random(n) < attack_rate).astype(np.int32)
    feats = np.empty((n, 5 * n_signals), np.float64)
    t = np.arange(window)
    for i in range(n):
        driver = np.sin(2 * np.pi * t / window * rng.uniform(0.5, 2.0) + rng.uniform(0, 6.28))
        sig = np.empty((n_signals, window))
        phase = rng.uniform(0, 6.28, n_signals)
        gain = rng.uniform(0.5, 1.5, n_signals)
        ar = rng.uniform(0.7, 0.95, n_signals)
        for s in range(n_signals):
            noise = rng.normal(0, 0.15, window)
            x = np.zeros(window)
            for k in range(1, window):
                x[k] = ar[s] * x[k - 1] + noise[k]
            sig[s] = gain[s] * np.roll(driver, int(phase[s] * 3)) + x
        if y[i]:
            # masquerade: victim signal replaced by replayed source + offset
            victim, src = rng.choice(n_signals, 2, replace=False)
            shift = rng.integers(1, window // 4)
            sig[victim] = np.roll(sig[src], shift) + offset
        f = []
        for s in range(n_signals):
            x = sig[s]
            dx = np.abs(np.diff(x))
            ac = np.corrcoef(x[:-1], x[1:])[0, 1] if x.std() > 1e-9 else 0.0
            c0 = np.corrcoef(x, sig[0])[0, 1] if s > 0 and x.std() > 1e-9 else 1.0
            f.extend([x.mean(), x.std(), dx.mean(), ac, c0])
        feats[i] = f
    feats = (feats - feats.mean(0)) / (feats.std(0) + 1e-9)
    return feats.astype(np.float32), y, y


@dataclass
class FederatedData:
    """Per-client tabular data + metadata used by utility scores.

    ``feature_shape`` is the structured shape of one example (product ==
    ``n_features``): ``None``/``(n_features,)`` for tabular features,
    ``(window, n_signals)`` for raw CAN windows — window-native model
    specs (``models/spec.py``) unflatten with it while the whole data path
    keeps moving flat ``[*, n_features]`` arrays.
    """

    x: List[np.ndarray]
    y: List[np.ndarray]
    test_x: np.ndarray
    test_y: np.ndarray
    n_features: int
    n_classes: int
    feature_shape: Optional[Tuple[int, ...]] = None

    @property
    def n_clients(self) -> int:
        return len(self.x)

    def data_sizes(self) -> np.ndarray:
        return np.array([len(xi) for xi in self.x], np.float32)

    def label_entropy(self) -> np.ndarray:
        """Per-client normalised label entropy — the data-quality proxy."""
        out = []
        for yi in self.y:
            p = np.bincount(yi, minlength=self.n_classes).astype(np.float64)
            p = p / max(p.sum(), 1)
            h = -(p[p > 0] * np.log(p[p > 0])).sum()
            out.append(h / np.log(self.n_classes))
        return np.asarray(out, np.float32)


def dirichlet_partition(rng: np.random.Generator, labels: np.ndarray, n_clients: int,
                        alpha: float, min_per_client: int = 8) -> List[np.ndarray]:
    """Label-skewed non-IID split (standard Dirichlet protocol)."""
    n_classes = int(labels.max()) + 1
    idx_by_class = [np.where(labels == c)[0] for c in range(n_classes)]
    for idx in idx_by_class:
        rng.shuffle(idx)
    client_idx: List[List[int]] = [[] for _ in range(n_clients)]
    for c, idx in enumerate(idx_by_class):
        if len(idx) == 0:
            continue
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for ci, part in enumerate(np.split(idx, cuts)):
            client_idx[ci].extend(part.tolist())
    # guarantee a minimum shard per client
    pool = [i for cl in client_idx for i in cl]
    for ci in range(n_clients):
        while len(client_idx[ci]) < min_per_client:
            client_idx[ci].append(int(rng.choice(pool)))
    return [np.asarray(sorted(c), np.int64) for c in client_idx]


def make_federated(
    seed: int,
    dataset: str = "unsw",
    n_samples: int = 20_000,
    n_clients: int = 40,
    alpha: float = 0.5,
    test_frac: float = 0.25,
    feature_shift: float = 0.15,
    label_noise_frac: float = 0.0,
    label_noise_rate: float = 0.4,
) -> FederatedData:
    """``label_noise_frac`` of the clients get ``label_noise_rate`` of their
    labels flipped — the low-data-quality clients whose exclusion is exactly
    what the paper's utility-based selection is for (random selection keeps
    sampling them; loss-seeking ACFL actively PREFERS them).

    ``dataset="road_raw"`` is the ROAD federation over *raw* window
    matrices (``road_like(raw=True)``): x stays flat for the data path,
    ``feature_shape=(window, n_signals)`` tells window-native models how to
    unflatten."""
    rng = np.random.default_rng(seed)
    feature_shape = None
    if dataset == "unsw":
        X, y_cat, y_bin = unsw_nb15_like(rng, n_samples)
        y = y_bin  # anomaly detection = binary task (paper metric: AUC-ROC)
    elif dataset == "road":
        X, y, _ = road_like(rng, n_samples)
    elif dataset == "road_raw":
        window, n_signals = 64, 6
        X, y, _ = road_like(rng, n_samples, window=window,
                            n_signals=n_signals, raw=True)
        feature_shape = (window, n_signals)
    else:
        raise ValueError(dataset)
    n_test = int(len(X) * test_frac)
    perm = rng.permutation(len(X))
    test_i, train_i = perm[:n_test], perm[n_test:]
    parts = dirichlet_partition(rng, y[train_i], n_clients, alpha)
    noisy_clients = set(
        rng.choice(n_clients, int(round(label_noise_frac * n_clients)),
                   replace=False).tolist()
    )
    xs, ys = [], []
    for ci, pi in enumerate(parts):
        gi = train_i[pi]
        shift = rng.normal(0, feature_shift, X.shape[1]).astype(np.float32)
        xs.append(X[gi] + shift)  # per-client covariate shift
        yi = y[gi].copy()
        if ci in noisy_clients:
            flip = rng.random(len(yi)) < label_noise_rate
            yi[flip] = 1 - yi[flip]  # binary labels
        ys.append(yi)
    return FederatedData(
        x=xs, y=ys, test_x=X[test_i], test_y=y[test_i],
        n_features=X.shape[1], n_classes=2, feature_shape=feature_shape,
    )


def round_batches(rng: np.random.Generator, fed: FederatedData, local_steps: int,
                  batch: int) -> Dict[str, np.ndarray]:
    """Sample per-round batches: leaves [n_clients, local_steps, batch, ...]."""
    n = fed.n_clients
    xs = np.empty((n, local_steps, batch, fed.n_features), np.float32)
    ys = np.empty((n, local_steps, batch), np.int32)
    for ci in range(n):
        idx = rng.integers(0, len(fed.x[ci]), (local_steps, batch))
        xs[ci] = fed.x[ci][idx]
        ys[ci] = fed.y[ci][idx]
    return {"x": xs, "y": ys}


# ---------------------------------------------------------------------------
# Device-side federation (for the lax.scan engine in train/fl_driver.py)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class StackedFederation:
    """Ragged per-client shards as one row-major device table.

    Row ``c * max_n + i`` of ``rows [n_clients * max_n, W]`` holds client
    ``c``'s sample ``i``: its ``d`` features in lanes ``0 … d-1``, its label
    as f32 in lane ``d`` (class ids are far below 2^24, so the round trip is
    exact) and zeros after, with ``W = round_up(d + 1, 128)``.  A drawn
    sample is then one contiguous, lane-aligned row, which the TPU gathers
    as a single 128-lane slice per draw; a batched ``[n_clients, max_n, d]``
    gather is laid out feature-major there, 42 values down one lane.  The
    table costs ``W / d`` times the bytes of the compact arrays (128 / 42
    ≈ 3× on UNSW, 512 / 384 on raw ROAD windows).

    ``sizes`` masks the padding: sampling draws indices in [0, sizes[i]) so
    the pad rows are never read.  This is the representation that lets batch
    sampling live *inside* a lowered round loop (no host sync per round).

    Registered as a pytree so the compiled engine takes it as a runtime
    argument: one compiled program serves every federation with the same
    shapes (the engine's runner cache keys on shapes, not data).
    """

    rows: jnp.ndarray     # [n_clients * max_n, W] f32 features | label | 0
    sizes: jnp.ndarray    # [n_clients] i32 valid rows per client
    test_x: jnp.ndarray   # [n_test, d] f32
    test_y: jnp.ndarray   # [n_test] i32

    @property
    def n_clients(self) -> int:
        return self.sizes.shape[0]

    @property
    def n_features(self) -> int:
        return self.test_x.shape[1]

    def shapes(self) -> Tuple:
        """Static fingerprint for compiled-program reuse."""
        return tuple((l.shape, str(l.dtype)) for l in
                     (self.rows, self.sizes, self.test_x, self.test_y))


jax.tree_util.register_dataclass(
    StackedFederation,
    data_fields=("rows", "sizes", "test_x", "test_y"),
    meta_fields=(),
)


@jax.jit
def _row_table(x, y):
    """[n, max_n, d] features + [n, max_n] labels -> [n * max_n, W] rows."""
    n, max_n, d = x.shape
    w = -(-(d + 1) // 128) * 128
    rows = jnp.concatenate([x, y[..., None].astype(jnp.float32)], axis=-1)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, w - d - 1)))
    return rows.reshape(n * max_n, w)


def stack_federation(fed: FederatedData) -> StackedFederation:
    """Pad the ragged client shards, upload them, and lay them out as the
    row table on the device (the compact arrays are not kept)."""
    max_n = max(len(xi) for xi in fed.x)
    xs = np.zeros((fed.n_clients, max_n, fed.n_features), np.float32)
    ys = np.zeros((fed.n_clients, max_n), np.int32)
    for ci, (xi, yi) in enumerate(zip(fed.x, fed.y)):
        xs[ci, : len(xi)] = xi
        ys[ci, : len(yi)] = yi
    return StackedFederation(
        rows=_row_table(jnp.asarray(xs), jnp.asarray(ys)),
        sizes=jnp.asarray(fed.data_sizes().astype(np.int32)),
        test_x=jnp.asarray(fed.test_x),
        test_y=jnp.asarray(fed.test_y),
    )


def sample_round_batches(key, stack: StackedFederation, local_steps: int,
                         batch: int) -> Dict[str, jnp.ndarray]:
    """jit-safe analogue of :func:`round_batches`: uniform with-replacement
    draws from each client's valid rows, leaves [n_clients, steps, batch, ...].

    Each draw reads one whole row of ``stack.rows``; features and label are
    split off it afterwards.
    """
    n, d = stack.n_clients, stack.n_features
    keys = jax.random.split(key, n)
    idx = jax.vmap(lambda k, size: jax.random.randint(
        k, (local_steps, batch), 0, size))(keys, stack.sizes)
    max_n = stack.rows.shape[0] // n
    r = stack.rows[jnp.arange(n, dtype=idx.dtype)[:, None, None] * max_n
                   + idx]
    return {"x": r[..., :d], "y": r[..., d].astype(jnp.int32)}


# ---------------------------------------------------------------------------
# Population-scale federation (ISSUE 6): lazy client shards over a shared
# sample pool, consumed by the cohort engine in train/fl_driver.py
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Population:
    """A 10^5–10^6-client federation without 10^5 materialised shards.

    :class:`StackedFederation` pads every client's samples into a
    ``[n_clients · max_n, W]`` row table — perfect at 8–40 clients,
    hopeless at 100k (100k × 500 × 128 f32 ≈ 25.6 GB before training
    starts).  A Population stores the DISTRIBUTION structure instead:

    * ``pool_x/pool_y`` — one shared sample pool (O(pool), not O(N));
    * ``member_idx [N, m] i32`` — each client's shard as rows into the
      pool (the lazy "materialisation": 100k × 32 i32 ≈ 13 MB);
    * ``member_size [N]`` — valid prefix per client (size heterogeneity);
    * per-client covariate shift applied ON DEVICE at batch-sampling time
      from ``fold_in(shift_key, client_id)`` — zero resident bytes for
      the one per-client tensor that scales with d.

    The per-client axis (``member_idx``, ``member_size``, ``data_size``,
    ``data_quality``) is what the population engine shards over the
    ``client`` mesh axis (``models/sharding.py::population_shardings``);
    the pool and test set replicate.  Registered as a pytree so the
    compiled engine takes it as a runtime argument like a
    StackedFederation; ``shapes()`` is the runner-cache fingerprint.
    Memory accounting for all of this lives in ``core/scale.py``
    (DESIGN.md §7).
    """

    pool_x: jnp.ndarray        # [pool, d] f32 shared sample pool (train)
    pool_y: jnp.ndarray        # [pool] i32
    member_idx: jnp.ndarray    # [n_clients, m] i32 rows into the pool
    member_size: jnp.ndarray   # [n_clients] i32 valid members (<= m)
    data_size: jnp.ndarray     # [n_clients] f32 normalised shard size
    data_quality: jnp.ndarray  # [n_clients] f32 label-entropy proxy
    shift_key: jnp.ndarray     # PRNG key: per-client covariate shift seed
    test_x: jnp.ndarray        # [n_test, d] f32
    test_y: jnp.ndarray        # [n_test] i32
    feature_shift: float = 0.15
    feature_shape: Optional[Tuple[int, ...]] = None

    @property
    def n_clients(self) -> int:
        return self.member_idx.shape[0]

    @property
    def n_features(self) -> int:
        return self.pool_x.shape[1]

    @property
    def n_classes(self) -> int:
        return 2

    @property
    def members_per_client(self) -> int:
        return self.member_idx.shape[1]

    def shapes(self) -> Tuple:
        """Static fingerprint for compiled-program reuse."""
        leaves = (self.pool_x, self.pool_y, self.member_idx,
                  self.member_size, self.data_size, self.data_quality,
                  self.test_x, self.test_y)
        return (tuple((l.shape, str(l.dtype)) for l in leaves),
                self.feature_shift, self.feature_shape)


jax.tree_util.register_dataclass(
    Population,
    data_fields=("pool_x", "pool_y", "member_idx", "member_size",
                 "data_size", "data_quality", "shift_key",
                 "test_x", "test_y"),
    meta_fields=("feature_shift", "feature_shape"),
)


def make_population(
    seed: int,
    dataset: str = "unsw",
    n_clients: int = 100_000,
    pool_samples: int = 8_000,
    members_per_client: int = 32,
    alpha: float = 0.5,
    test_frac: float = 0.25,
    feature_shift: float = 0.15,
    chunk_clients: int = 16_384,
) -> Population:
    """Generate a :class:`Population` lazily: the client axis is built in
    ``chunk_clients``-sized NumPy chunks (membership draws, row shuffles,
    entropy) so peak host memory is O(chunk × m), never O(N × samples) —
    a million-client population streams through ~64 chunks.

    Non-IID structure matches :func:`make_federated` in kind: per-client
    Beta(α, α) label propensity (the binary Dirichlet) decides each
    client's attack share, membership rows are drawn from the matching
    class buckets of the pool, and the per-client covariate shift is
    deferred to on-device sampling (``sample_cohort_batches``) via
    ``fold_in(shift_key, client_id)``.
    """
    rng = np.random.default_rng(seed)
    feature_shape = None
    if dataset == "unsw":
        X, _, y = unsw_nb15_like(rng, pool_samples)
    elif dataset == "road":
        X, y, _ = road_like(rng, pool_samples)
    elif dataset == "road_raw":
        window, n_signals = 64, 6
        X, y, _ = road_like(rng, pool_samples, window=window,
                            n_signals=n_signals, raw=True)
        feature_shape = (window, n_signals)
    else:
        raise ValueError(dataset)
    n_test = int(len(X) * test_frac)
    perm = rng.permutation(len(X))
    test_i, train_i = perm[:n_test], perm[n_test:]
    Xtr, ytr = X[train_i], y[train_i]

    buckets = [np.flatnonzero(ytr == c) for c in (0, 1)]
    if any(len(b) == 0 for b in buckets):
        raise ValueError("pool has an empty class — enlarge pool_samples")

    m = int(members_per_client)
    member_size = rng.integers(max(m // 2, 1), m + 1,
                               n_clients).astype(np.int32)
    member_idx = np.empty((n_clients, m), np.int32)
    quality = np.empty((n_clients,), np.float32)
    for lo in range(0, n_clients, chunk_clients):
        hi = min(lo + chunk_clients, n_clients)
        c = hi - lo
        p1 = rng.beta(alpha, alpha, c)                    # binary Dirichlet
        n1 = rng.binomial(m, p1)
        cols = np.arange(m)[None, :]
        is1 = cols < n1[:, None]                          # [c, m] class plan
        rows = np.where(
            is1,
            buckets[1][rng.integers(0, len(buckets[1]), (c, m))],
            buckets[0][rng.integers(0, len(buckets[0]), (c, m))],
        )
        # shuffle within each row so the member_size prefix stays a fair
        # mix of the client's classes
        order = rng.random((c, m)).argsort(axis=1)
        rows = np.take_along_axis(rows, order, axis=1)
        member_idx[lo:hi] = rows
        lab = ytr[rows]                                   # [c, m]
        valid = cols < member_size[lo:hi][:, None]
        p = (lab * valid).sum(1) / np.maximum(member_size[lo:hi], 1)
        p = np.clip(p, 1e-9, 1 - 1e-9)
        quality[lo:hi] = -(p * np.log(p) + (1 - p) * np.log(1 - p)) / np.log(2)

    sizes = member_size.astype(np.float32)
    return Population(
        pool_x=jnp.asarray(Xtr),
        pool_y=jnp.asarray(ytr.astype(np.int32)),
        member_idx=jnp.asarray(member_idx),
        member_size=jnp.asarray(member_size),
        data_size=jnp.asarray(sizes / sizes.mean()),
        data_quality=jnp.asarray(quality),
        shift_key=jax.random.key(np.uint32(seed) ^ np.uint32(0x5CA1E)),
        test_x=jnp.asarray(X[test_i]),
        test_y=jnp.asarray(y[test_i].astype(np.int32)),
        feature_shift=float(feature_shift),
        feature_shape=feature_shape,
    )


def sample_cohort_batches(key, pop: Population, cohort_idx,
                          local_steps: int, batch: int) -> Dict[str, jnp.ndarray]:
    """The cohort gather: batches for the SELECTED clients only, leaves
    ``[k_max, local_steps, batch, ...]`` — per-round data traffic is
    O(k_max · steps · batch · d), independent of the population size
    (that independence is the population engine's sublinear-wall claim,
    gated in benchmarks/bench_scale.py).

    Each cohort slot gathers its membership row, draws uniform
    with-replacement sample indices from its valid prefix, gathers those
    pool rows, and adds the client's covariate shift — generated on the
    fly from ``fold_in(shift_key, client_id)``, so the shift is a stable
    per-client property that never occupies [N, d] resident memory.
    """
    k = cohort_idx.shape[0]
    keys = jax.random.split(key, k)
    d = pop.pool_x.shape[1]
    mem = pop.member_idx[cohort_idx]
    msize = pop.member_size[cohort_idx]

    def per_slot(kk, mem_i, size_i, ci):
        j = jax.random.randint(kk, (local_steps, batch), 0,
                               jnp.maximum(size_i, 1))
        rows = mem_i[j]
        shift = pop.feature_shift * jax.random.normal(
            jax.random.fold_in(pop.shift_key, ci), (d,))
        return pop.pool_x[rows] + shift, pop.pool_y[rows]

    xs, ys = jax.vmap(per_slot)(keys, mem, msize, cohort_idx)
    return {"x": xs, "y": ys}
