"""Multiprocess DataLoader workers.

Reference behavior: io/dataloader/dataloader_iter.py:365
(_DataLoaderIterMultiProcess) + worker.py — worker subprocesses pull
index batches from per-worker queues, collate, and push result batches
through a shared data queue; the parent reorders and (TPU-native twist)
performs the host->device transfer itself, so device state never crosses
a process boundary.  The transfer doubles as device prefetch: jax
dispatch is async, so converting batch N+1 while batch N is being
consumed overlaps H2D with compute (the role of the reference's
buffered reader / pin-memory thread).

Workers run pure-Python dataset code only — no jax — which keeps fork()
safe even with an initialized backend in the parent.
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue as queue_mod
import traceback
from typing import Any, Callable, List, Optional

import numpy as np

from ..observability.events import default_ring, stamp
from ..profiler.utils import RecordEvent, TracerEventType

__all__ = ["np_collate", "MultiprocessBatchIterator"]


def np_collate(batch: List[Any]):
    """default_collate that stays in numpy (picklable, no device)."""
    sample = batch[0]
    if hasattr(sample, "numpy") and not isinstance(sample, np.ndarray):
        # framework Tensor leaked into a worker: convert to host numpy
        # before pickling (device handles must not cross processes)
        return np.stack([np.asarray(s.numpy()) for s in batch])
    if isinstance(sample, (np.ndarray, np.generic)):
        return np.stack(batch)
    if isinstance(sample, (int, float)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [np_collate(list(items)) for items in transposed]
    if isinstance(sample, dict):
        return {k: np_collate([d[k] for d in batch]) for k in sample}
    return batch


class _WorkerError:
    def __init__(self, exc):
        self.msg = "".join(traceback.format_exception(exc))


def _to_numpy_tree(x):
    """Strip any framework Tensors a custom collate_fn produced."""
    if hasattr(x, "numpy") and not isinstance(x, (np.ndarray, np.generic)):
        return np.asarray(x.numpy())
    if isinstance(x, (list, tuple)):
        return [_to_numpy_tree(v) for v in x]
    if isinstance(x, dict):
        return {k: _to_numpy_tree(v) for k, v in x.items()}
    return x


_SHM_SENTINEL = "__shm__"


def _worker_loop(dataset, index_queue, data_queue, collate_fn,
                 worker_init_fn, worker_id, num_workers, base_seed,
                 shm_name=None, shm_bytes=0):
    """Reference: dataloader/worker.py _worker_loop."""
    np.random.seed((base_seed + worker_id) % (2 ** 32))
    ring = None
    try:
        import paddle_tpu.io as _io  # set get_worker_info() state
        _io._worker_info = _io._WorkerInfo(
            id=worker_id, num_workers=num_workers, dataset=dataset)
        if worker_init_fn is not None:
            worker_init_fn(worker_id)
        if shm_name is not None:
            from . import shm as _shm
            ring = _shm.ShmRing(shm_name, shm_bytes, owner=False)
    except Exception as e:  # noqa: BLE001
        data_queue.put((-1, _WorkerError(e)))
        return
    while True:
        try:
            job = index_queue.get()
        except (EOFError, KeyboardInterrupt):
            return
        if job is None:  # shutdown sentinel
            return
        batch_idx, idx_batch = job
        try:
            samples = [dataset[i] for i in idx_batch]
            batch = _to_numpy_tree(collate_fn(samples))
            if ring is not None:
                from . import shm as _shm
                ring.push(_shm.pack_tree(batch))
                # control message only; payload went through this
                # worker's FIFO ring, so (sentinel, wid) is enough for
                # the parent to pop the matching record
                data_queue.put((batch_idx, (_SHM_SENTINEL, worker_id)))
            else:
                data_queue.put((batch_idx, batch))
        except Exception as e:  # noqa: BLE001
            data_queue.put((batch_idx, _WorkerError(e)))


_shm_tag_counter = [0]


class MultiprocessBatchIterator:
    """Iterates collated numpy batches produced by worker processes, in
    submission order.  ``to_device`` (applied in the parent) converts
    each batch as soon as it is reordered — async H2D prefetch."""

    def __init__(self, dataset, batch_indices, collate_fn=None,
                 num_workers: int = 2, prefetch_factor: int = 2,
                 worker_init_fn: Optional[Callable] = None,
                 timeout: float = 0,
                 to_device: Optional[Callable] = None,
                 mp_context: Optional[str] = None,
                 use_shared_memory: Optional[bool] = None,
                 shm_ring_bytes: int = 64 << 20):
        # ``dataloader.start``: from here — the shared-memory rings, the
        # worker processes' spawn — to the first batch handed out; once
        # per iterator, a span for an open profiler session and ONE ring
        # event with its end on both clocks (a set-up lies before any
        # session a benchmark opens)
        self._start_t0 = stamp()[0]
        self._start_span = RecordEvent("dataloader.start",
                                       TracerEventType.Dataloader)
        self._start_span.begin()
        self._batches = list(batch_indices)
        self._collate = collate_fn or np_collate
        self._timeout = timeout or None
        self._to_device = to_device or (lambda x: x)
        # default start method is SPAWN: fork() of a process whose jax
        # runtime already started worker threads can deadlock in the
        # child (the suite's "os.fork() incompatible with JAX threads"
        # warnings).  Workers run pure-Python dataset code, so the only
        # spawn cost is startup latency; fork remains available via
        # mp_context="fork" / PADDLE_TPU_MP_CONTEXT for fork-safe hosts.
        env_method = os.environ.get("PADDLE_TPU_MP_CONTEXT")
        method = mp_context or env_method or "spawn"
        explicit = mp_context is not None or env_method is not None
        if method == "spawn" and not explicit:
            # spawn needs picklable worker payloads; closure-defined
            # datasets get the (riskier) fork path with a notice rather
            # than a crash deep inside Process.start.  An EXPLICIT
            # spawn request is honored as-is (and will raise there).
            # The probe discards bytes as they are produced — no full
            # serialized copy of a large in-memory dataset.
            import pickle

            class _Null:
                def write(self, _):
                    return None

            try:
                pickle.Pickler(_Null(), protocol=pickle.HIGHEST_PROTOCOL
                               ).dump((dataset, self._collate,
                                       worker_init_fn))
            except Exception:
                import warnings
                warnings.warn(
                    "DataLoader: dataset/collate_fn/worker_init_fn is "
                    "not picklable, so worker processes fall back to "
                    "fork() (unsafe if the jax runtime already started "
                    "threads).  Define them at module level to use the "
                    "spawn default.", RuntimeWarning, stacklevel=3)
                method = "fork"
        ctx = mp.get_context(method)
        self._num_workers = max(1, num_workers)
        self._data_queue = ctx.Queue()
        self._index_queues = []
        self._procs = []
        # shared-memory payload path (reference use_shared_memory=True);
        # on by default whenever the native ring is available
        self._rings = []
        if use_shared_memory is None:
            use_shared_memory = os.environ.get(
                "PADDLE_TPU_USE_SHM", "1") == "1"
        if use_shared_memory:
            try:
                from . import shm as _shm
                if _shm.shm_available():
                    # process-wide counter: names stay unique across all
                    # concurrently-alive loaders in this process
                    _shm_tag_counter[0] += 1
                    tag = f"/pt_dl_{os.getpid()}_{_shm_tag_counter[0]}"
                    self._rings = [
                        _shm.ShmRing(f"{tag}_{wid}", shm_ring_bytes,
                                     owner=True)
                        for wid in range(self._num_workers)]
            except Exception:  # noqa: BLE001 - fall back to queue payloads
                self._rings = []
        # which payload transport is LIVE ("shm": the native ring;
        # "queue": pickled through the result queue) — the fallback
        # stays for users, but it is never silent
        self.transport = "shm" if self._rings else "queue"
        base_seed = int.from_bytes(os.urandom(4), "little")
        for wid in range(self._num_workers):
            iq = ctx.Queue()
            shm_name = self._rings[wid].name if self._rings else None
            p = ctx.Process(
                target=_worker_loop,
                args=(dataset, iq, self._data_queue, self._collate,
                      worker_init_fn, wid, self._num_workers, base_seed,
                      shm_name, shm_ring_bytes),
                daemon=True)
            p.start()
            self._index_queues.append(iq)
            self._procs.append(p)
        self._send_idx = 0
        self._rcvd_idx = 0
        self._reorder = {}
        depth = self._num_workers * max(prefetch_factor, 2)
        for _ in range(min(depth, len(self._batches))):
            self._dispatch()

    def _dispatch(self):
        if self._send_idx < len(self._batches):
            wid = self._send_idx % self._num_workers
            self._index_queues[wid].put(
                (self._send_idx, self._batches[self._send_idx]))
            self._send_idx += 1

    def __iter__(self):
        return self

    def _started(self):
        span, self._start_span = self._start_span, None
        found = {"num_workers": self._num_workers,
                 "transport": self.transport}
        span.annotate(**found)
        span.end()
        at = stamp()
        default_ring().emit("dataloader.start", at=at,
                            dur_s=at[0] - self._start_t0, **found)

    def __next__(self):
        try:
            return self._next()
        finally:
            if self._start_span is not None:
                self._started()

    def _next(self):
        if self._rcvd_idx >= len(self._batches):
            self.shutdown()
            raise StopIteration
        with RecordEvent("dataloader.next", TracerEventType.Dataloader):
            with RecordEvent("dataloader.wait",
                             TracerEventType.Dataloader):
                self._await_batch()
            batch = self._reorder.pop(self._rcvd_idx)
            self._rcvd_idx += 1
            self._dispatch()
            with RecordEvent("dataloader.to_device",
                             TracerEventType.Dataloader):
                return self._to_device(batch)

    def _await_batch(self):
        """Block on the data queue until the next batch in order is in
        ``_reorder``."""
        waited = 0.0
        while self._rcvd_idx not in self._reorder:
            try:
                idx, payload = self._data_queue.get(timeout=5.0)
            except queue_mod.Empty:
                waited += 5.0
                dead = [p for p in self._procs if not p.is_alive()]
                if dead:
                    self.shutdown()
                    raise RuntimeError(
                        "DataLoader worker exited abnormally (exit "
                        f"codes {[p.exitcode for p in dead]})") from None
                if self._timeout and waited >= self._timeout:
                    self.shutdown()
                    raise RuntimeError(
                        f"DataLoader worker timed out after "
                        f"{self._timeout}s") from None
                continue
            if isinstance(payload, _WorkerError):
                self.shutdown()
                raise RuntimeError(
                    "DataLoader worker raised:\n" + payload.msg)
            if isinstance(payload, tuple) and len(payload) == 2 and \
                    isinstance(payload[0], str) and \
                    payload[0] == _SHM_SENTINEL:
                from . import shm as _shm
                blob = self._rings[payload[1]].pop(timeout=30.0)
                if blob is None:
                    self.shutdown()
                    raise RuntimeError(
                        "DataLoader shm ring timed out fetching a batch")
                payload = _shm.unpack_tree(blob)
            self._reorder[idx] = payload

    def shutdown(self):
        for iq in self._index_queues:
            try:
                iq.put(None)
            except Exception:  # noqa: BLE001
                pass
        for p in self._procs:
            p.join(timeout=1.0)
            if p.is_alive():
                p.terminate()
        self._procs = []
        for r in self._rings:
            try:
                r.close()
            except Exception:  # noqa: BLE001
                pass
        self._rings = []

    def __del__(self):
        try:
            self.shutdown()
        except Exception:  # noqa: BLE001
            pass
