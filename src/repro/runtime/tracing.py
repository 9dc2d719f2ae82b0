"""Host spans of the serving loop, on the clock the device trace shares.

``span(name, **counts)`` marks one phase of host work.  It opens a
``jax.profiler.TraceAnnotation`` (inert unless a profiler session runs;
when one does, the phase sits on the timeline of the device's
operations), stamps ``time.monotonic()`` — the clock of
``Request.t_*`` — at entry and exit, and appends one :class:`Record` to
a bounded in-memory log.  The log needs no profiler: a reader takes
``records(t0, t1)`` of any window it stamped on the same clock.

Spans nest per thread: a record's ``parent`` is the ``seq`` of the span
that was open around it.  ``counts`` are what the phase handled (rows,
pages, tokens); the context manager yields the dict, so counts known
only inside the block are added there.

Spans belong in host code.  The body of a jitted function runs once,
while it is traced, and a span there would time the trace, not the
step.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import List, NamedTuple, Optional

import jax

CAPACITY = 65_536


class Record(NamedTuple):
    seq: int                  # order of opening; children name it
    name: str
    start: float              # time.monotonic() at entry
    end: float                # ... and at exit
    parent: Optional[int]     # seq of the enclosing span, None at the top
    counts: dict


class _Span:
    __slots__ = ("_rec", "_name", "_counts", "_seq", "_parent", "_start",
                 "_annotation")

    def __init__(self, recorder: "Recorder", name: str, counts: dict):
        self._rec, self._name, self._counts = recorder, name, counts

    def __enter__(self) -> dict:
        stack = self._rec._stack()
        self._seq = next(self._rec._seqs)
        self._parent = stack[-1] if stack else None
        stack.append(self._seq)
        self._annotation = jax.profiler.TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._start = time.monotonic()
        return self._counts

    def __exit__(self, *exc) -> None:
        end = time.monotonic()
        self._annotation.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._append(Record(self._seq, self._name, self._start, end,
                                 self._parent, self._counts))


class Recorder:
    """A bounded log of closed spans.  When full it drops the oldest
    record and counts it in ``dropped``; :meth:`records` refuses a
    window it no longer holds whole."""

    def __init__(self, capacity: int = CAPACITY):
        self._log = collections.deque(maxlen=capacity)
        self._seqs = itertools.count()
        self._local = threading.local()
        self._lock = threading.Lock()
        self.dropped = 0
        self._dropped_end = float("-inf")   # latest end of a dropped record

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, rec: Record) -> None:
        with self._lock:
            if len(self._log) == self._log.maxlen:
                self.dropped += 1
                self._dropped_end = max(self._dropped_end, self._log[0].end)
            self._log.append(rec)

    def span(self, name: str, **counts) -> _Span:
        return _Span(self, name, counts)

    def records(self, t0: float, t1: float) -> Optional[List[Record]]:
        """The records lying wholly inside ``[t0, t1]``, in the order
        they closed; None when a record that ended at or after ``t0``
        was dropped (the window is no longer held whole)."""
        with self._lock:
            if self._dropped_end >= t0:
                return None
            log = list(self._log)
        return [r for r in log if r.start >= t0 and r.end <= t1]


#: the program's one recorder
RECORDER = Recorder()
span = RECORDER.span
records = RECORDER.records
