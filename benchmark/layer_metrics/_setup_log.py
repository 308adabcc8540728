"""What the five ``setup_*`` readers share; no metric of its own
(``read`` finds nothing, so a cell that runs every file here passes
over it).

The program keeps a log of what it traced, lowered and compiled
(``paddle_tpu.observability.compile_log``) and names its loader's start
in ``default_ring()``, both stamped on the Unix epoch.  A set-up is what
lies BEFORE THE WINDOW, and the readers run after the reference has
compiled its own ~200 programs, so they keep what ENDED before the
traced slice began: the slice lies inside the window, and
``counters["compiles_in_window"]`` says whether anything compiled between
the window's start and the slice — where it is not 0 (or not counted)
the split would be wrong and the readers read nothing; nor do they
where the log has dropped records (its list is bounded and the oldest,
the set-up's, go first).

A trace's lines run on a clock of their own that starts with the
profiler session (on the v5e runtime as on the CPU's: a host line's
``timestamp_ns`` is a few hundred microseconds, not an epoch); the
session's start on the epoch is ``profile_start_time`` of the file's
``Task Environment`` plane.  A program from before the log (a parent
checkout) has no such module: nothing to read.
"""

import functools
import os

from benchmark import harness, xplane, xplane_meta

TASK_PLANE = "Task Environment"
EPOCH_FROM_S = 1e9          # a timestamp past 2001 is no session clock


def read(trace, counters, spans, cell):
    return None


@functools.lru_cache(maxsize=2)
def _profile_start_s(path: str, mtime_ns: int, size: int):
    with open(path, "rb") as f:
        data = f.read()
    for num, plane in xplane_meta.fields(data):
        if num != 1:
            continue
        name, _, _, stat_names = xplane_meta._plane(plane)
        if name != TASK_PLANE:
            continue
        for n, v in xplane_meta.fields(plane):
            if n == 6:
                key, value = xplane_meta._stat(v, stat_names)
                if key == "profile_start_time":
                    return value * 1e-9
    return None


def slice_start_epoch_s(cell, trace):
    """The earliest host or device timestamp of the slice that the run
    of ``cell`` has just recorded, in Unix-epoch seconds; None where
    there is no slice or its clock cannot be placed."""
    mt = xplane_meta.of_cell(cell, trace)
    if mt is None:
        return None
    starts = [h.start_s for h in mt.host[:1]]       # sorted by start
    starts += [ops[0].start_s for ops in mt.ops.values() if ops]
    if not starts:
        return None
    first = min(starts)
    if first >= EPOCH_FROM_S:
        return first
    path = xplane.find_xplane(harness.run_dir(cell) + "/trace")
    st = os.stat(path)
    session = _profile_start_s(path, st.st_mtime_ns, st.st_size)
    return None if session is None else session + first


def before_the_window(trace, counters, cell):
    """``(compile_log, until_epoch_s)``, or None where the readers have
    nothing to read (see the module's text)."""
    if counters.get("compiles_in_window") != 0:
        return None
    try:
        from paddle_tpu.observability import compile_log
    except ImportError:
        return None
    if compile_log.totals()["dropped"]:
        return None         # the oldest records, the set-up's, fell out
    until = slice_start_epoch_s(cell, trace)
    return None if until is None else (compile_log, until)


def step_row(compile_log, until: float):
    """``(the train step's row, all rows)`` of the log's programs before
    ``until``: the program named ``step`` (what ``make_train_step``
    jits), else the largest by seconds."""
    rows = compile_log.by_program(until_epoch_s=until)
    named = [r for r in rows if r["program"] == "step"]
    return (named or rows or [None])[0], rows
