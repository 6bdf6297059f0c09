"""Device-resident padded client data bank (the batched FL engine's input).

The legacy FL loop re-pads and re-uploads every scheduled device's shard from
host on every round (one ``local_update`` host round-trip per device).  The
bank pays that cost exactly once: all M shards are padded to a common batch
grid and uploaded as two device-resident tensors

    xb: (M, n_batches, batch_size, *feat)   x_train.dtype
    yb: (M, n_batches, batch_size, *lab)    int32, -1 marks padding

where ``feat``/``lab`` are whatever trailing shape the dataset carries —
``(D,)`` flat image features with scalar labels (the paper's MNIST-like
setup), or ``(S,)`` token rows with ``(S,)`` next-token labels
(:func:`repro.data.tokens.make_token_dataset`).  A round is a K-row gather
(``xb[dev_idx]``) inside the jitted round step instead of K host->device
copies.  Padding positions carry label -1, the validity convention every
FLModel loss masks on, so a shard shorter than the common grid trains
identically to its legacy per-shard padding: the extra all-padding batches
produce exactly-zero gradients and leave the parameters untouched.

Memory: the bank is the dataset re-laid-out per device plus padding up to
the *largest* shard's batch count, i.e. O(M * max_k ceil(|D_k|/bs) * bs *
prod(feat)) elements — at paper scale (M=300, MNIST-like) tens of MB, but a
skewed Dirichlet partition at large M pads every client to the single
largest shard and the bill grows as M * max_k instead of sum_k.  ``build``
warns (``ClientBank.nbytes`` / :func:`_device_memory_limit`) when the
padded bank would claim more than ``DEFAULT_MEM_FRACTION`` of the
accelerator's memory and points at :class:`BucketedClientBank`, which
groups clients into power-of-two batch-count buckets so within-bucket
padding is bounded below 2x.

The same gather idiom serves per-round *evaluation*: :class:`EvalBank`
keeps the test set resident on device, and :func:`eval_sample_plan`
precomputes a seeded (T, n) row-index plan so a client-sampled eval is one
gather + batched forward inside the jitted round step (or the scanned
horizon) — with ``frac = 1`` the gather is skipped entirely and the eval
is bit-identical to the full-test-set accuracy call it replaces.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils import spans

EVAL_SEED_OFFSET = 23
# decorrelates the eval-sampling stream from the model-init / channel /
# scheduling streams that consume FLConfig.seed (the scheduling permutation
# already claims +17 — see scheduling.RandomPolicy.SEED_OFFSET)

DEFAULT_MEM_FRACTION = 0.5
# fraction of the device's reported memory a padded bank may claim before
# ``build`` warns and recommends the bucketed layout


def _device_memory_limit() -> "int | None":
    """Device memory in bytes, or None when the backend doesn't report it
    (CPU).  Separated out so tests can monkeypatch a limit in."""
    try:
        stats = jax.devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    return stats.get("bytes_limit")


def _check_bank_memory(projected_bytes: int, mem_fraction: float) -> None:
    limit = _device_memory_limit()
    if limit is None or limit <= 0:
        return
    if projected_bytes > mem_fraction * limit:
        warnings.warn(
            f"padded ClientBank would hold {projected_bytes / 2**20:.0f} MiB "
            f"(> {mem_fraction:.0%} of the device's {limit / 2**20:.0f} MiB):"
            f" skewed shard sizes pad every client to the largest shard; "
            f"use FLConfig(client_bank='bucketed') (BucketedClientBank) to "
            f"bound the padding, or shrink the dataset / batch grid",
            ResourceWarning,
            stacklevel=3,
        )


def _padded_arrays(x_train, y_train, shards, batch_size, nb):
    """Shared shard->grid layout: (m, nb*bs, *trail) arrays, -1 label pad."""
    m = len(shards)
    bs = int(batch_size)
    xb = np.zeros((m, nb * bs, *x_train.shape[1:]), x_train.dtype)
    yb = np.full((m, nb * bs, *y_train.shape[1:]), -1, np.int32)
    for k, idx in enumerate(shards):
        n = len(idx)
        xb[k, :n] = x_train[idx]
        yb[k, :n] = y_train[idx]
    feat, lab = x_train.shape[1:], y_train.shape[1:]
    return (
        xb.reshape(m, nb, bs, *feat),
        yb.reshape(m, nb, bs, *lab),
    )


@dataclasses.dataclass
class ClientBank:
    """All M client shards, padded and resident on device."""

    xb: jax.Array        # (M, NB, BS, *feat) x_train dtype
    yb: jax.Array        # (M, NB, BS, *lab) int32; -1 marks padding
    sizes: np.ndarray    # (M,) realized shard sizes (host, for FedAvg weights)

    @property
    def num_devices(self) -> int:
        return self.xb.shape[0]

    @property
    def batch_size(self) -> int:
        return self.xb.shape[2]

    @property
    def nbytes(self) -> int:
        """Device bytes the bank holds (both tensors, padding included)."""
        return int(self.xb.nbytes) + int(self.yb.nbytes)

    @staticmethod
    def _ceil_batches(n: int, batch_size: int) -> int:
        """The grid rule: batches needed to cover n samples (min 1)."""
        return max(1, int(-(-int(n) // int(batch_size))))

    def n_batches_for(self, devs) -> int:
        """Batches covering the given devices' shards — the batched engine
        slices the global grid down to this per round (same rule as
        ``build``, single owner), clamped to the bank's own grid."""
        if not len(devs):
            return 1
        need = self._ceil_batches(self.sizes[list(devs)].max(), self.batch_size)
        return min(need, self.xb.shape[1])

    @classmethod
    def build(
        cls, x_train: np.ndarray, y_train: np.ndarray, shards: list,
        batch_size: int, *, mem_fraction: float = DEFAULT_MEM_FRACTION,
    ) -> "ClientBank":
        """Pad all shards once to the common (n_batches, batch_size) grid.

        Sample order inside each shard is preserved (shards arrive
        pre-shuffled from the partitioner), so batch b of device k holds
        exactly the samples the legacy ``local_update`` would put there.
        Works for any trailing feature/label shape: flat image rows with
        scalar labels, or (S,) token rows with (S,) shifted labels.
        """
        m = len(shards)
        bs = int(batch_size)
        sizes = np.array([len(s) for s in shards], dtype=np.intp)
        nb = cls._ceil_batches(sizes.max(), bs) if m else 1
        itemsize = np.dtype(x_train.dtype).itemsize
        feat = int(np.prod(x_train.shape[1:], dtype=np.int64)) if x_train.ndim > 1 else 1
        lab = int(np.prod(y_train.shape[1:], dtype=np.int64)) if y_train.ndim > 1 else 1
        projected = m * nb * bs * (feat * itemsize + lab * 4)
        _check_bank_memory(projected, mem_fraction)
        xb, yb = _padded_arrays(x_train, y_train, shards, bs, nb)
        bank = cls(xb=jnp.asarray(xb), yb=jnp.asarray(yb), sizes=sizes)
        spans.count("bank.bytes_uploaded", bank.nbytes)
        return bank


@dataclasses.dataclass
class BucketedClientBank:
    """Size-bucketed client banks: pow-2 batch grids instead of one max grid.

    Clients are grouped by ``next_pow2(ceil(|D_k| / bs))``, and each bucket
    is padded only to its own power-of-two batch count, so within-bucket
    padding is bounded below 2x the client's own need — a skewed Dirichlet
    partition stops billing every small client for the single largest
    shard.  A round's K-row gather now spans several buckets, so it runs
    as per-bucket gathers + a batch-axis pad/slice to the round's common
    ``nb`` + an inverse permutation back to schedule order
    (:meth:`gather`, device-side).  The gathered rows are element-equal to
    the padded bank's ``xb[devs, :nb]``, so training through either layout
    is bit-identical (pinned in tests/test_client_bank.py).

    Batched per-round engine only: the scan horizon indexes one dense
    (M, NB, ...) tensor inside the traced program and cannot span buckets.
    """

    buckets: list        # list of (xb, yb) device-array pairs, (m_b, NB_b, BS, ...)
    bucket_of: np.ndarray   # (M,) bucket index per client
    row_of: np.ndarray      # (M,) row of the client inside its bucket
    sizes: np.ndarray       # (M,) realized shard sizes

    @property
    def num_devices(self) -> int:
        return len(self.sizes)

    @property
    def batch_size(self) -> int:
        return self.buckets[0][0].shape[2]

    @property
    def nbytes(self) -> int:
        return sum(int(xb.nbytes) + int(yb.nbytes) for xb, yb in self.buckets)

    def n_batches_for(self, devs) -> int:
        """Same single-owner grid rule as :meth:`ClientBank.n_batches_for`,
        clamped to the largest bucket grid."""
        if not len(devs):
            return 1
        need = ClientBank._ceil_batches(
            self.sizes[list(devs)].max(), self.batch_size
        )
        cap = max(xb.shape[1] for xb, _ in self.buckets)
        return min(need, cap)

    def gather(self, devs, nb: int):
        """Gather the scheduled rows as (K, nb, BS, ...) device tensors.

        Per-bucket gather, pad/slice every bucket's batch axis to the
        round's ``nb`` (pad rows carry label -1 — the shared validity
        convention, so they are exactly-zero-gradient), then invert the
        bucket-order permutation so row k is device ``devs[k]``.
        """
        devs = np.asarray(devs, dtype=np.intp)
        order = np.argsort(self.bucket_of[devs], kind="stable")
        inv = np.argsort(order, kind="stable")
        xs, ys = [], []
        for b in devs[order]:
            xb, yb = self.buckets[self.bucket_of[b]]
            row = int(self.row_of[b])
            x, y = xb[row], yb[row]
            have = x.shape[0]
            if have >= nb:
                x, y = x[:nb], y[:nb]
            else:
                pad = nb - have
                x = jnp.concatenate(
                    [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0
                )
                y = jnp.concatenate(
                    [y, jnp.full((pad, *y.shape[1:]), -1, y.dtype)], axis=0
                )
            xs.append(x)
            ys.append(y)
        x = jnp.stack(xs)[jnp.asarray(inv)]
        y = jnp.stack(ys)[jnp.asarray(inv)]
        return x, y

    @classmethod
    def build(
        cls, x_train: np.ndarray, y_train: np.ndarray, shards: list,
        batch_size: int, *, mem_fraction: float = DEFAULT_MEM_FRACTION,
    ) -> "BucketedClientBank":
        del mem_fraction  # bucketing IS the remedy; accepted for API parity
        bs = int(batch_size)
        sizes = np.array([len(s) for s in shards], dtype=np.intp)
        need = np.array(
            [ClientBank._ceil_batches(n, bs) for n in sizes], dtype=np.intp
        )
        pow2 = 1 << np.ceil(np.log2(need)).astype(np.intp)
        levels = sorted(set(int(p) for p in pow2))
        bucket_of = np.zeros(len(shards), np.intp)
        row_of = np.zeros(len(shards), np.intp)
        buckets = []
        for bi, nb in enumerate(levels):
            members = [k for k in range(len(shards)) if int(pow2[k]) == nb]
            bucket_of[members] = bi
            row_of[members] = np.arange(len(members))
            xb, yb = _padded_arrays(
                x_train, y_train, [shards[k] for k in members], bs, nb
            )
            buckets.append((jnp.asarray(xb), jnp.asarray(yb)))
        bank = cls(
            buckets=buckets, bucket_of=bucket_of, row_of=row_of, sizes=sizes
        )
        spans.count("bank.bytes_uploaded", bank.nbytes)
        return bank


@dataclasses.dataclass
class EvalBank:
    """The test set, resident on device for gathered per-round evaluation.

    No padding: a sampled eval gathers exactly ``n`` rows (fixed shape per
    horizon), so the masked-accuracy bookkeeping the training bank needs
    never enters the eval path and the ``frac = 1`` case stays bit-identical
    to the full accuracy call over the raw arrays.
    """

    xe: jax.Array        # (N, *feat)
    ye: jax.Array        # (N, *lab)

    @property
    def num_samples(self) -> int:
        return self.xe.shape[0]

    @property
    def nbytes(self) -> int:
        """Device bytes the bank holds (both tensors)."""
        return int(self.xe.nbytes) + int(self.ye.nbytes)

    @classmethod
    def build(cls, x_test: np.ndarray, y_test: np.ndarray) -> "EvalBank":
        bank = cls(xe=jnp.asarray(x_test), ye=jnp.asarray(y_test))
        spans.count("bank.bytes_uploaded", bank.nbytes)
        return bank


def eval_sample_plan(
    num_test: int, frac: float, num_rounds: int, seed: int
) -> "np.ndarray | None":
    """Seeded (T, n) eval-row gather plan, or ``None`` for a full eval.

    One draw per round for *every* round (not only eval rounds), so the
    per-round driver and the scanned horizon — which may skip different
    rounds under ``eval_every`` — index an identical plan at matching ``t``
    and report identical sampled accuracies.  n = ceil(frac * N), without
    replacement within a round.
    """
    if frac >= 1.0:
        return None
    n = max(1, int(np.ceil(frac * num_test)))
    rng = np.random.default_rng(seed + EVAL_SEED_OFFSET)
    return np.stack(
        [rng.choice(num_test, size=n, replace=False) for _ in range(num_rounds)]
    ).astype(np.int32)
