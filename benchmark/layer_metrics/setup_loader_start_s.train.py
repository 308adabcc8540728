"""Seconds from the construction of the DataLoader's multi-worker
iterator (the shared-memory rings, the worker processes' spawn and their
imports) to the first batch handed out: the ring event
``dataloader.start`` of ``paddle_tpu/io/worker.py``, the first that
ended before the traced slice."""

from benchmark.layer_metrics import _setup_log


def read(trace, counters, spans, cell):
    until = _setup_log.slice_start_epoch_s(cell, trace)
    if until is None:
        return None
    try:
        from paddle_tpu.observability import default_ring
    except ImportError:
        return None
    for ev in default_ring().recent():
        if ev["name"] == "dataloader.start" and \
                ev.get("epoch_ns", until * 1e9 + 1) <= until * 1e9:
            return ev["dur_s"]
    return None
