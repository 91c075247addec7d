"""DataSetIterator plumbing, incl. background prefetch.

Reference: `deeplearning4j-nn/.../datasets/iterator/` —
`AsyncDataSetIterator.java:36` (background thread + LinkedBlockingDeque:68),
`MultipleEpochsIterator`, `ExistingDataSetIterator`,
`impl/ListDataSetIterator`.

TPU note: AsyncDataSetIterator is the host-side half of the infeed pipeline —
it overlaps host ETL with device compute, which is what hides HBM transfer
latency behind the previous step's execution (the reference wraps every
`fit()` iterator the same way, `MultiLayerNetwork.java:982`).
"""
from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator, List, Optional

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet


class DataSetIterator:
    """Base iterator contract (reference ND4J `DataSetIterator`)."""

    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        return self

    def __next__(self) -> DataSet:
        if not self.has_next():
            raise StopIteration
        return self.next()

    def has_next(self) -> bool:
        raise NotImplementedError

    def next(self) -> DataSet:
        raise NotImplementedError

    def reset(self) -> None:
        raise NotImplementedError

    def batch(self) -> int:
        raise NotImplementedError

    @property
    def async_supported(self) -> bool:
        return True


class ListDataSetIterator(DataSetIterator):
    """Iterate a pre-batched list (reference `impl/ListDataSetIterator`)."""

    def __init__(self, data: List[DataSet], batch_size: Optional[int] = None):
        if batch_size is not None and len(data) == 1:
            data = data[0].batch_by(batch_size)
        self._data = list(data)
        self._pos = 0

    def has_next(self):
        return self._pos < len(self._data)

    def next(self):
        d = self._data[self._pos]
        self._pos += 1
        return d

    def reset(self):
        self._pos = 0

    def batch(self):
        return self._data[0].num_examples() if self._data else 0


class ExistingDataSetIterator(DataSetIterator):
    """Wrap any python iterable of DataSets (reference
    `ExistingDataSetIterator.java`)."""

    def __init__(self, iterable: Iterable[DataSet]):
        self._iterable = iterable
        # a one-shot iterator (generator) cannot be replayed by reset()
        self._one_shot = iter(iterable) is iterable
        self._consumed = False
        self._it: Optional[Iterator[DataSet]] = None
        self._peek: Optional[DataSet] = None

    def reset(self):
        if self._one_shot:
            if self._consumed:
                raise ValueError(
                    "ExistingDataSetIterator wraps a one-shot iterator "
                    "(generator) that has already been consumed; pass a list "
                    "or a restartable iterable to train multiple epochs")
            self._it = self._iterable  # type: ignore[assignment]
        else:
            self._it = iter(self._iterable)
        self._peek = None

    def has_next(self):
        if self._it is None:
            self.reset()
        if self._peek is not None:
            return True
        try:
            self._peek = next(self._it)  # type: ignore[arg-type]
            self._consumed = True
            return True
        except StopIteration:
            return False

    def next(self):
        if not self.has_next():
            raise StopIteration
        d, self._peek = self._peek, None
        return d

    def batch(self):
        return -1


def natural_key(key: str):
    """Sort key treating digit runs numerically: s_9 < s_10 < s_11 —
    shard writers number files, often without zero padding; lexicographic
    order would interleave them. Shared by FileDataSetIterator and
    cloud.storage.StorageDataSetIterator."""
    import re

    return [int(p) if p.isdigit() else p
            for p in re.split(r"(\d+)", key)]


class FileDataSetIterator(DataSetIterator):
    """Iterate DataSets lazily from exported files — the path-based half
    of the reference's export-staged training (reference
    `FileSplitDataSetIterator.java` / `ExistingMiniBatchDataSetIterator`):
    only one file's arrays are in memory at a time, so the training set
    may be far larger than host RAM.

    `paths`: an iterable of file paths, a single file path, or a
    directory (every `*.npz` inside, digit runs sorted numerically so
    externally produced unpadded names keep write order: shard_9 <
    shard_10 — same rule as `StorageDataSetIterator`)."""

    def __init__(self, paths):
        import os

        if isinstance(paths, (str, os.PathLike)):
            if os.path.isdir(paths):
                self.paths = sorted(
                    (os.path.join(paths, f) for f in os.listdir(paths)
                     if f.endswith(".npz")), key=natural_key)
            else:
                # a single exported shard, not an iterable of its chars
                self.paths = [os.fspath(paths)]
        else:
            self.paths = [os.fspath(p) for p in paths]
        if not self.paths:
            raise ValueError("no exported dataset files to iterate")
        self._pos = 0

    def reset(self):
        self._pos = 0

    def has_next(self):
        return self._pos < len(self.paths)

    def next(self):
        if not self.has_next():
            raise StopIteration
        ds = DataSet.load(self.paths[self._pos])
        self._pos += 1
        return ds

    def batch(self):
        return -1


class MultipleEpochsIterator(DataSetIterator):
    """Replay an underlying iterator N times (reference
    `MultipleEpochsIterator.java`)."""

    def __init__(self, epochs: int, underlying: DataSetIterator):
        self.epochs = epochs
        self._under = underlying
        self._epoch = 0

    def reset(self):
        self._under.reset()
        self._epoch = 0

    def has_next(self):
        if self._under.has_next():
            return True
        if self._epoch + 1 < self.epochs:
            self._epoch += 1
            self._under.reset()
            return self._under.has_next()
        return False

    def next(self):
        if not self.has_next():
            raise StopIteration
        return self._under.next()

    def batch(self):
        return self._under.batch()


_SENTINEL = object()


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch (reference `AsyncDataSetIterator.java:36`:
    producer thread feeding a bounded blocking queue, default capacity 2 —
    here `queue_size`). The producer runs host-side ETL while the device
    executes the previous step."""

    def __init__(self, underlying: DataSetIterator, queue_size: int = 2):
        self._under = underlying
        self._queue_size = queue_size
        self._queue: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._peek = None
        self._exhausted = False
        # producer starts lazily on first has_next() so that the __iter__ →
        # reset() handshake doesn't consume-and-discard a prefetch pass
        # (load-bearing for one-shot generator sources)

    def _start(self):
        self._queue = queue.Queue(maxsize=self._queue_size)
        self._exhausted = False
        self._peek = None

        def worker(q: queue.Queue, under: DataSetIterator):
            try:
                while under.has_next():
                    q.put(under.next())
            except Exception as e:  # surface producer errors to the consumer
                q.put(e)
                return
            q.put(_SENTINEL)

        self._thread = threading.Thread(
            target=worker, args=(self._queue, self._under), daemon=True)
        self._thread.start()

    def reset(self):
        # drain + stop the producer; restart happens lazily on next pull
        # (reference `AsyncDataSetIterator.reset`)
        if self._thread is not None:
            if not self._exhausted:  # sentinel not yet consumed: drain to it
                while True:
                    item = self._queue.get()
                    if item is _SENTINEL or isinstance(item, Exception):
                        break
            self._thread.join()
            self._thread = None
        self._peek = None
        self._exhausted = False
        self._under.reset()

    def has_next(self):
        if self._peek is not None:
            return True
        if self._exhausted:
            return False
        if self._thread is None:
            self._start()
        item = self._queue.get()
        if item is _SENTINEL:
            self._exhausted = True
            return False
        if isinstance(item, Exception):
            self._exhausted = True
            raise item
        self._peek = item
        return True

    def next(self):
        if not self.has_next():
            raise StopIteration
        d, self._peek = self._peek, None
        return d

    def batch(self):
        return self._under.batch()


class AsyncMultiDataSetIterator(AsyncDataSetIterator):
    """Background-thread prefetch over a MultiDataSet iterator (reference
    `AsyncMultiDataSetIterator.java` — same producer/bounded-queue scheme as
    `AsyncDataSetIterator.java:36`, element type MultiDataSet). The producer
    contract here is source-agnostic (`has_next`/`next`), so the multi-input
    variant only differs in what flows through the queue."""


class IteratorDataSetIterator(DataSetIterator):
    """Re-batches an iterator of (possibly variously sized) DataSets to a
    fixed batch size (reference `IteratorDataSetIterator.java`)."""

    def __init__(self, source: Iterable[DataSet], batch_size: int):
        self._source = source
        self.batch_size = batch_size
        # one-shot iterators (generators) can't replay across epochs —
        # same guard as ExistingDataSetIterator
        self._one_shot = iter(source) is source
        self._consumed = False
        self._iter: Optional[Iterator[DataSet]] = None
        self._buf: List[DataSet] = []
        self._buffered = 0
        self._peek: Optional[DataSet] = None

    def reset(self) -> None:
        if self._one_shot:
            if self._consumed:
                raise ValueError(
                    "IteratorDataSetIterator wraps a one-shot iterator "
                    "(generator) that has already been consumed; pass a "
                    "list or a restartable iterable to train multiple epochs")
            self._iter = self._source  # type: ignore[assignment]
            self._consumed = True
        else:
            self._iter = iter(self._source)
        self._buf, self._buffered, self._peek = [], 0, None

    def _assemble(self) -> Optional[DataSet]:
        while self._buffered < self.batch_size:
            try:
                ds = next(self._iter)
            except StopIteration:
                break
            self._buf.append(ds)
            self._buffered += ds.num_examples()
        if not self._buf:
            return None
        merged = DataSet.merge(self._buf)  # preserves both mask arrays
        self._buf, take = [], self.batch_size

        def sl(a, lo, hi):
            return None if a is None else a[lo:hi]

        n = merged.num_examples()
        if n > take:  # keep the tail for the next batch
            self._buf = [DataSet(merged.features[take:],
                                 sl(merged.labels, take, n),
                                 sl(merged.features_mask, take, n),
                                 sl(merged.labels_mask, take, n))]
            self._buffered = n - take
            return DataSet(merged.features[:take], sl(merged.labels, 0, take),
                           sl(merged.features_mask, 0, take),
                           sl(merged.labels_mask, 0, take))
        self._buffered = 0
        return merged

    def has_next(self) -> bool:
        if self._iter is None:
            self.reset()
        if self._peek is None:
            self._peek = self._assemble()
        return self._peek is not None

    def next(self) -> DataSet:
        if not self.has_next():
            raise StopIteration
        ds, self._peek = self._peek, None
        return ds

    def batch(self) -> int:
        return self.batch_size


class SingletonMultiDataSetIterator:
    """Yields one MultiDataSet forever-resettable (reference
    `impl/SingletonMultiDataSetIterator.java`)."""

    def __init__(self, mds):
        self._mds = mds
        self._done = False

    def __iter__(self):
        self._done = False
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        self._done = True
        return self._mds

    def reset(self) -> None:
        self._done = False

    @property
    def async_supported(self) -> bool:
        return False


class DeviceCacheDataSetIterator(DataSetIterator):
    """Upload a pre-batched dataset to the device ONCE and iterate the
    resident copies (any number of epochs for free).

    The TPU-native answer to a slow host link: small benchmark datasets
    (MNIST 47 MB, CIFAR-10 180 MB) fit in HBM many times over, so paying
    the host→HBM transfer per epoch — let alone per step — is pure
    waste. Batches keep their compact wire dtypes (uint8
    pixels, int ids); the compiled step casts/normalizes on device exactly
    as it does for host-fed batches, so training is bit-identical.
    """

    def __init__(self, data, batch_size=None):
        import jax

        if not isinstance(data, list):
            data = list(data)
        if batch_size is not None and len(data) == 1:
            data = data[0].batch_by(batch_size)

        def put(a):
            return None if a is None else jax.device_put(a)

        def int_range(a, mask=None):
            """(min, max) of an integer array while it is still host-side
            — the fit-path range validation consumes this instead of
            downloading the resident batch every step (masked positions
            exempt: sentinel-id padding is legal under a labels mask)."""
            if a is None:
                return None
            arr = np.asarray(a)
            if not np.issubdtype(arr.dtype, np.integer) or not arr.size:
                return None
            if mask is not None:
                arr = arr[np.asarray(mask).astype(bool).reshape(arr.shape)]
                if not arr.size:
                    return None
            return (int(arr.min()), int(arr.max()))

        staged = []
        for d in data:
            ds = DataSet(put(d.features), put(d.labels),
                         put(d.features_mask), put(d.labels_mask))
            ds._value_ranges = {
                "features": int_range(d.features),
                "labels": int_range(d.labels, d.labels_mask),
            }
            staged.append(ds)
        self._data = staged
        self._pos = 0
        # force the uploads to COMPLETE now (device_put is async, and over
        # a remote transport block_until_ready is not a reliable barrier):
        # one scalar that depends on every staged buffer, materialized host-
        # side, so the first training pass never waits on a transfer
        import jax.numpy as jnp

        arrs = [a for d in self._data
                for a in (d.features, d.labels, d.features_mask,
                          d.labels_mask) if a is not None]
        if arrs:
            # full reductions: a single-element read is not enough on a
            # lazy remote transport — only consuming every element forces
            # the complete buffers across
            tot = sum(jnp.sum(a.astype(jnp.float32)) for a in arrs)
            float(tot)

    def has_next(self):
        return self._pos < len(self._data)

    def next(self):
        d = self._data[self._pos]
        self._pos += 1
        return d

    def reset(self):
        self._pos = 0

    def batch(self):
        return self._data[0].num_examples() if self._data else 0

    @property
    def async_supported(self):
        return False  # already resident: a prefetch thread adds nothing


class QuarantiningDataSetIterator(DataSetIterator):
    """Screens every batch of an underlying iterator for non-finite
    features/labels/masks (`optimize.health.non_finite_batch_reason`) and
    diverts poisoned batches to a `optimize.health.BatchQuarantine` —
    with provenance — instead of letting them reach the fit loop. The
    data-iterator tier of the training health sentinel: any fit loop
    (single-node, FaultTolerantTrainer, worker pools) gets poison
    screening by wrapping its iterator, no network changes needed.

        it = QuarantiningDataSetIterator(base_iterator, "quarantine/")
        net.fit(it, epochs=3)
        it.quarantined  # records diverted so far (across epochs)

    Lookahead note: `has_next` must not claim a batch it would then
    quarantine, so the wrapper pre-pulls until it holds a CLEAN batch or
    the underlying iterator is exhausted."""

    def __init__(self, underlying, quarantine, max_quarantined: int = 256):
        from deeplearning4j_tpu.optimize.health import BatchQuarantine

        self._u = underlying
        self.quarantine = (quarantine if isinstance(quarantine,
                                                    BatchQuarantine)
                           else BatchQuarantine(
                               quarantine, max_records=max_quarantined))
        self.quarantined = 0
        self._pos = 0  # position in the CURRENT pass (provenance)
        self._pending: Optional[DataSet] = None

    def _advance(self) -> None:
        from deeplearning4j_tpu.optimize.health import (
            non_finite_batch_reason,
        )

        while self._pending is None and self._u.has_next():
            ds = self._u.next()
            pos = self._pos
            self._pos += 1
            reason = non_finite_batch_reason(ds)
            if reason is None:
                self._pending = ds
                return
            self.quarantine.quarantine(
                ds, reason, {"stream_position": pos,
                             "stage": "iterator"})
            self.quarantined += 1

    def has_next(self) -> bool:
        self._advance()
        return self._pending is not None

    def next(self) -> DataSet:
        self._advance()
        if self._pending is None:
            raise StopIteration
        ds, self._pending = self._pending, None
        return ds

    def reset(self) -> None:
        self._pending = None
        self._pos = 0
        self._u.reset()

    def batch(self) -> int:
        return self._u.batch()

    @property
    def async_supported(self) -> bool:
        # the screen runs host-side per batch; keep ordering deterministic
        return False
