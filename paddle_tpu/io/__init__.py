"""paddle_tpu.io — Dataset / DataLoader / samplers.

Reference: python/paddle/io/ — Dataset (reader.py), DataLoader
(reader.py:216), multiprocess workers (dataloader_iter.py:365),
DistributedBatchSampler.

The loader uses a thread-pool prefetch pipeline instead of the reference's
fork+shared-memory workers: on TPU the feed bottleneck is host→device
transfer, which jax overlaps when the next batch is materialised while the
device computes; numpy collation holds the GIL only briefly.  A
``num_workers>0`` request maps to a ``ThreadPoolExecutor`` of that size with
``prefetch_factor`` batches in flight.
"""

from __future__ import annotations

import itertools
import math
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable, List, Optional, Sequence

import numpy as np

from ..tensor.tensor import Tensor, to_tensor

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
           "ChainDataset", "ConcatDataset", "Subset", "random_split",
           "Sampler", "SequenceSampler", "RandomSampler", "WeightedRandomSampler",
           "BatchSampler", "DistributedBatchSampler", "DataLoader",
           "default_collate_fn", "get_worker_info", "SubsetRandomSampler"]


class Dataset:
    """Map-style dataset (reference: io/dataset.py)."""

    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors: Sequence[Tensor]):
        lens = {t.shape[0] for t in tensors}
        if len(lens) != 1:
            raise ValueError("tensors must share dim 0")
        self.tensors = list(tensors)

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for d in self.datasets:
            yield from d


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cumsizes = np.cumsum([len(d) for d in self.datasets]).tolist()

    def __len__(self):
        return self.cumsizes[-1]

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        di = int(np.searchsorted(self.cumsizes, idx, side="right"))
        prev = 0 if di == 0 else self.cumsizes[di - 1]
        return self.datasets[di][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    total = len(dataset)
    if all(isinstance(l, float) for l in lengths):
        counts = [int(math.floor(total * l)) for l in lengths]
        rem = total - sum(counts)
        for i in range(rem):
            counts[i % len(counts)] += 1
        lengths = counts
    if sum(lengths) != total:
        raise ValueError("sum of lengths != dataset size")
    perm = np.random.permutation(total).tolist()
    out, off = [], 0
    for n in lengths:
        out.append(Subset(dataset, perm[off:off + n]))
        off += n
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class SubsetRandomSampler(Sampler):
    def __init__(self, indices, generator=None):
        super().__init__(None)
        self.indices = list(indices)

    def __iter__(self):
        return iter(np.random.permutation(self.indices).tolist())

    def __len__(self):
        return len(self.indices)


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        super().__init__(None)
        self.weights = np.asarray(
            weights.numpy() if isinstance(weights, Tensor) else weights,
            dtype="float64")
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        super().__init__(dataset)
        if sampler is None:
            sampler = RandomSampler(dataset) if shuffle else \
                SequenceSampler(dataset)
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Reference: io/sampler.py DistributedBatchSampler — shards the index
    stream across data-parallel ranks."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from ..distributed import get_world_size, get_rank
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else \
            get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(
            math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = np.arange(n).tolist()
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        indices += indices[: (self.total_size - n)]
        local = indices[self.local_rank::self.nranks]
        batch = []
        for idx in local:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


class _WorkerInfo:
    def __init__(self, id=0, num_workers=1, dataset=None):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = None


def get_worker_info():
    return _worker_info


def default_collate_fn(batch: List[Any]):
    sample = batch[0]
    if isinstance(sample, (np.ndarray, np.generic)):
        return to_tensor(np.stack(batch))
    if isinstance(sample, Tensor):
        from ..tensor.manipulation import stack
        return stack(batch, axis=0)
    if isinstance(sample, (int, float)):
        return to_tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [default_collate_fn(list(items)) for items in transposed]
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch])
                for k in sample}
    return batch


def _tree_to_tensor(batch):
    """numpy batch structure -> Tensor structure (host->device)."""
    if isinstance(batch, (np.ndarray, np.generic)):
        return to_tensor(batch)
    if isinstance(batch, (list, tuple)):
        return [_tree_to_tensor(b) for b in batch]
    if isinstance(batch, dict):
        return {k: _tree_to_tensor(v) for k, v in batch.items()}
    return batch


class DataLoader:
    """Reference: io/reader.py:216."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(prefetch_factor, 2)
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = persistent_workers
        # how the LAST iteration moved batches: "shm" (worker
        # processes + native shared-memory ring), "queue" (worker
        # processes, pickled payloads — the ring was unavailable),
        # "threads" or "inline"; None before the first iteration
        self.transport = None
        if persistent_workers:
            import warnings
            warnings.warn(
                "persistent_workers=True is accepted but workers are "
                "(re)spawned per epoch in this implementation",
                stacklevel=2)
        self.is_iterable_ds = isinstance(dataset, IterableDataset)
        if self.is_iterable_ds:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            if batch_size is None:
                self.batch_sampler = None
                self.batch_size = None
            else:
                self.batch_sampler = BatchSampler(
                    dataset, shuffle=shuffle, batch_size=batch_size,
                    drop_last=drop_last)

    def __len__(self):
        if self.is_iterable_ds:
            raise TypeError("IterableDataset has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    def _iter_batches(self):
        if self.is_iterable_ds:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        elif self.batch_sampler is None:
            # batch_size=None: auto-batching disabled; yield raw samples
            for i in range(len(self.dataset)):
                yield self.dataset[i]
        else:
            for idx_batch in self.batch_sampler:
                samples = [self.dataset[i] for i in idx_batch]
                yield self.collate_fn(samples)

    def __iter__(self):
        if self.num_workers == 0 or self.is_iterable_ds:
            self.transport = "inline"
            yield from self._iter_batches()
            return
        if self.use_shared_memory and self.batch_sampler is not None:
            # multiprocess workers (reference dataloader_iter.py:365):
            # workers collate to numpy; the parent does the host->device
            # transfer, which doubles as async device prefetch
            from .worker import MultiprocessBatchIterator, np_collate
            worker_collate = self.collate_fn \
                if self.collate_fn is not default_collate_fn else np_collate
            it = MultiprocessBatchIterator(
                self.dataset, list(self.batch_sampler),
                collate_fn=worker_collate,
                num_workers=self.num_workers,
                prefetch_factor=self.prefetch_factor,
                worker_init_fn=self.worker_init_fn,
                timeout=self.timeout, to_device=_tree_to_tensor)
            self.transport = it.transport
            try:
                yield from it
            finally:
                it.shutdown()
            return
        # thread-pool prefetch pipeline (use_shared_memory=False path)
        self.transport = "threads"
        pool = ThreadPoolExecutor(max_workers=self.num_workers)
        try:
            sampler_iter = iter(self.batch_sampler)
            pending = []
            depth = self.num_workers * self.prefetch_factor

            def fetch(idx_batch):
                samples = [self.dataset[i] for i in idx_batch]
                return self.collate_fn(samples)

            for idx_batch in itertools.islice(sampler_iter, depth):
                pending.append(pool.submit(fetch, idx_batch))
            while pending:
                fut = pending.pop(0)
                nxt = next(sampler_iter, None)
                if nxt is not None:
                    pending.append(pool.submit(fetch, nxt))
                yield fut.result()
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
