"""Naturally-partitioned synthetic federated datasets.

The paper's four tasks use naturally partitioned datasets whose client sizes
are heavily skewed (Fig. 2: OpenImage, Google Speech, Shakespeare, Reddit).
We reproduce the *distributional* structure with deterministic synthetic data:

* per-task client-dataset-size distributions (lognormal / zipf, parameters
  matched to Fig. 2's shape: medians of tens of samples, tails of thousands),
* deterministic per-client example generation via ``jax.random.fold_in`` so
  any client's data can be materialized anywhere (a property real FL
  simulators get from the dataset partition files),
* non-IID label/token skew per client (Dirichlet over classes), so federated
  optimization behaves like the paper's tasks rather than an IID toy.

Clients below one full batch are excluded (paper §5.1).
"""

from __future__ import annotations

from contextlib import nullcontext
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["TaskSpec", "TASK_DISTRIBUTIONS", "FederatedDataset",
           "make_federated_dataset"]


@dataclass(frozen=True)
class TaskSpec:
    """Distributional + modality description of one FL task."""

    name: str
    kind: str                 # 'tokens' | 'image' | 'audio' | 'embeddings'
    n_clients: int
    batch_size: int           # paper A.1 batch sizes
    size_dist: str            # 'lognormal' | 'zipf'
    size_mu: float = 3.5      # lognormal mean of log(samples)
    size_sigma: float = 1.2
    zipf_a: float = 1.6
    size_min: int = 1
    size_max: int = 100_000
    n_classes: int = 0        # for labelled tasks
    dirichlet_alpha: float = 0.3


# Parameters chosen to match Fig. 2's shapes: Shakespeare (648 clients, long
# tail to ~1e4), OpenImage (13771 clients, median ~60), Google Speech (2168
# speakers, tight around ~70), Reddit (1.6M clients, zipf with most clients
# tiny).  Batch sizes from paper A.1.
TASK_DISTRIBUTIONS: dict[str, TaskSpec] = {
    "tg": TaskSpec(name="tg", kind="tokens", n_clients=648, batch_size=4,
                   size_dist="lognormal", size_mu=5.0, size_sigma=1.4,
                   size_max=16_000, n_classes=0),
    "ic": TaskSpec(name="ic", kind="image", n_clients=13_771, batch_size=20,
                   size_dist="lognormal", size_mu=4.1, size_sigma=1.0,
                   size_max=10_000, n_classes=596),
    "sr": TaskSpec(name="sr", kind="audio", n_clients=2_168, batch_size=20,
                   size_dist="lognormal", size_mu=4.2, size_sigma=0.6,
                   size_max=4_000, n_classes=35),
    "mlm": TaskSpec(name="mlm", kind="tokens", n_clients=1_600_000, batch_size=20,
                    size_dist="zipf", zipf_a=1.35, size_max=60_000, n_classes=0),
    # kanana2-a3b: the MLM population, 4 sequences a batch (the vocabulary
    # slice and the sequence length are the task's, set by build_engine).
    "kanana2-a3b": TaskSpec(name="kanana2-a3b", kind="tokens",
                            n_clients=1_600_000, batch_size=4,
                            size_dist="zipf", zipf_a=1.35, size_max=60_000,
                            n_classes=0),
    # LM-architecture FL tasks (the assigned archs trained federatedly).
    "lm": TaskSpec(name="lm", kind="tokens", n_clients=100_000, batch_size=8,
                   size_dist="lognormal", size_mu=4.5, size_sigma=1.3,
                   size_max=50_000, n_classes=0),
}


class FederatedDataset:
    """Deterministic synthetic federated dataset.

    Client sizes are sampled once (seeded); example *content* is generated
    lazily per (client, index) with fold_in, so memory stays O(1) per client
    until batches are materialized — the fix for FedScale's load-everything
    design the paper criticizes (§2.5).
    """

    def __init__(self, spec: TaskSpec, *, seed: int = 1337,
                 vocab_size: int = 32_000, seq_len: int = 128,
                 input_dim: int = 64):
        self.spec = spec
        self.seed = seed
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.input_dim = input_dim
        rng = np.random.default_rng(seed)
        n = spec.n_clients
        if spec.size_dist == "lognormal":
            sizes = rng.lognormal(mean=spec.size_mu, sigma=spec.size_sigma, size=n)
        elif spec.size_dist == "zipf":
            sizes = rng.zipf(a=spec.zipf_a, size=n).astype(np.float64)
        else:
            raise ValueError(spec.size_dist)
        sizes = np.clip(sizes, spec.size_min, spec.size_max).astype(np.int64)
        # Paper §5.1: exclude clients that cannot fill a single batch.
        sizes = np.maximum(sizes, spec.batch_size)
        self.sizes = sizes
        # Per-client class skew (labelled tasks): Dirichlet mixture weights.
        if spec.n_classes:
            self._class_logits = rng.dirichlet(
                [spec.dirichlet_alpha] * spec.n_classes, size=min(n, 65_536))
        else:
            self._class_logits = None

    # -- population statistics (placement features) ------------------------
    @property
    def n_clients(self) -> int:
        return self.spec.n_clients

    def n_samples(self, cid: int) -> int:
        return int(self.sizes[cid % len(self.sizes)])

    def n_batches(self, cid: int) -> int:
        """x in the paper: ceil(samples / batch_size), drop-last=False."""
        bs = self.spec.batch_size
        return max(1, int(self.n_samples(cid)) // bs)

    # -- deterministic content ---------------------------------------------
    def _key(self, cid, batch_idx):
        """Per-(client, batch) PRNG key; ``cid``/``batch_idx`` may be Python
        ints (one-off path) or traced int32 arrays (bulk path) — fold_in is
        elementwise either way, so both paths draw identical keys."""
        k = jax.random.key(self.seed)
        k = jax.random.fold_in(k, cid % (2 ** 31 - 1))
        return jax.random.fold_in(k, batch_idx)

    def _content(self, cid, batch_idx, offset, probs, bs: int, sl: int) -> dict:
        """One batch of content from its key.  Pure and traceable: the single
        source of truth for both :meth:`client_batch` and the vectorized
        :meth:`gather_batches` (which vmaps it), keeping the two bit-identical.

        ``offset`` (tokens) and ``probs`` (labelled tasks) are precomputed on
        the host because they involve int64 modular arithmetic / table rows
        indexed by cid — passing them in keeps the traced math 32-bit safe.
        """
        key = self._key(cid, batch_idx)
        kind = self.spec.kind
        if kind == "tokens":
            # Client-specific unigram skew: tokens drawn from a client-biased
            # slice of the vocab (non-IID token distribution).
            k1, k2 = jax.random.split(key)
            base = jax.random.randint(k1, (bs, sl), 0, self.vocab_size)
            tokens = (base // 4 + offset) % self.vocab_size
            return {"tokens": tokens.astype(jnp.int32)}
        if kind in ("image", "audio", "embeddings"):
            k1, k2 = jax.random.split(key)
            x = jax.random.normal(k1, (bs, self.input_dim), dtype=jnp.float32)
            if probs is not None:
                y = jax.random.choice(k2, self.spec.n_classes, shape=(bs,),
                                      p=jnp.asarray(probs))
                # Make the task learnable: shift inputs by a class-dependent
                # direction so labels are predictable from content.
                dirs = jax.random.normal(jax.random.key(7), (self.spec.n_classes,
                                                             self.input_dim))
                x = x + 2.0 * dirs[y]
                return {"x": x, "y": y.astype(jnp.int32)}
            return {"x": x}
        raise ValueError(kind)

    def _token_offset(self, cids):
        """Host-side (int64-safe) client vocab offset for the tokens tasks."""
        return (np.asarray(cids, dtype=np.int64) * 2_654_435_761) % max(
            self.vocab_size // 4, 1)

    def client_batch(self, cid: int, batch_idx: int, *, batch_size=None,
                     seq_len=None) -> dict:
        """Materialize one batch of this client's data.

        Implemented as a size-1 :meth:`gather_batches` so the one-off and
        bulk paths run the *same* compiled program — guaranteeing the
        vectorized round packer is bit-identical to per-batch fetching
        (eager vs jit can differ by an FMA-fusion ULP otherwise).
        """
        out = self.gather_batches(np.asarray([cid]), np.asarray([batch_idx]),
                                  batch_size=batch_size, seq_len=seq_len)
        return {k: v[0] for k, v in out.items()}

    # -- bulk fetch (the round packer's fast path) -------------------------
    def gather_batches(self, cids, batch_idxs, *, batch_size=None,
                       seq_len=None, fetch_span=None) -> dict:
        """Materialize many (client, batch) pairs in one fused device call.

        Returns ``{name: [N, ...]}`` bit-identical to stacking N
        :meth:`client_batch` calls, at a fraction of the host cost: the
        per-batch Python/dispatch overhead (the round-loop bottleneck this
        replaces) collapses into one jitted vmap.  The jit cache is bounded
        by rounding N up to the next power of two (extra rows are computed
        for (0, 0) and sliced off).

        ``fetch_span``: a context entered around the host's blocking fetch
        of the generator's output (the engine's ``wait.generator`` span);
        the generator's dispatch stays outside it.
        """
        cids = np.asarray(cids, dtype=np.int64)
        bis = np.asarray(batch_idxs, dtype=np.int64)
        if cids.shape != bis.shape or cids.ndim != 1:
            raise ValueError("cids and batch_idxs must be equal-length 1-D")
        n = cids.shape[0]
        if n == 0:
            sample = self.client_batch(0, 0, batch_size=batch_size,
                                       seq_len=seq_len)
            return {k: np.zeros((0,) + np.shape(v), np.asarray(v).dtype)
                    for k, v in sample.items()}
        bs = batch_size or self.spec.batch_size
        sl = seq_len or self.seq_len
        m = 1 << (n - 1).bit_length()          # pow2-bucketed jit shapes
        pad = m - n
        if pad:
            cids = np.concatenate([cids, np.zeros(pad, np.int64)])
            bis = np.concatenate([bis, np.zeros(pad, np.int64)])
        cid32 = (cids % (2 ** 31 - 1)).astype(np.int32)
        bi32 = bis.astype(np.int32)
        args = [jnp.asarray(cid32), jnp.asarray(bi32)]
        if self.spec.kind == "tokens":
            args.append(jnp.asarray(self._token_offset(cids).astype(np.int32)))
        elif self.spec.n_classes and self._class_logits is not None:
            rows = cids % len(self._class_logits)
            args.append(jnp.asarray(self._class_logits[rows],
                                    dtype=jnp.float32))
        out = self._bulk_fn(bs, sl)(*args)
        with fetch_span or nullcontext():
            return {k: np.asarray(v)[:n] for k, v in out.items()}

    def _bulk_fn(self, bs: int, sl: int):
        cache = getattr(self, "_bulk_cache", None)
        if cache is None:
            cache = self._bulk_cache = {}
        fn = cache.get((bs, sl))
        if fn is None:
            kind = self.spec.kind
            labelled = bool(self.spec.n_classes) and \
                self._class_logits is not None

            def elem(cid32, bi32, extra=None):
                # cid32 is already reduced mod 2**31-1, so _key's traced
                # ``cid % (2**31-1)`` is a no-op and matches the host path.
                if kind == "tokens":
                    return self._content(cid32, bi32, extra, None, bs, sl)
                return self._content(cid32, bi32, 0,
                                     extra if labelled else None, bs, sl)

            def gen_batches(cid32, bi32, *extra):
                # Named so the program reads ``jit_gen_batches`` in a
                # profiler trace.
                return jax.vmap(elem)(cid32, bi32, *extra)

            fn = cache[(bs, sl)] = jax.jit(gen_batches)
        return fn


def make_federated_dataset(task: str, *, seed: int = 1337, **overrides
                           ) -> FederatedDataset:
    spec = TASK_DISTRIBUTIONS[task]
    field_names = set(TaskSpec.__dataclass_fields__)
    spec_over = {k: v for k, v in overrides.items() if k in field_names}
    ds_over = {k: v for k, v in overrides.items() if k not in field_names}
    if spec_over:
        spec = replace(spec, **spec_over)
    return FederatedDataset(spec, seed=seed, **ds_over)
