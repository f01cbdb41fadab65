"""Spans and counters of one answer of an evaluation engine.

An engine entry (``CompiledSweep.mva``, ``.transient``, ``.execute`` and
the module functions behind them) opens a root span, ``repro.<engine>``,
and a child span around each layer of its host work: lowering, probes,
class streams, dispatch, waiting on the device, pulling results to the
host and reducing them.  Each span is written twice:

* into the profiler's trace as a ``jax.profiler.TraceAnnotation``, so
  that in a profile it sits on a host line on the device trace's clock
  and each idle gap of the device can be put down to the span around it;
* into an in-memory record of the root: the self time of every span name
  under it (its duration less that of its child spans) and counters such
  as the bytes pulled from the device.  ``recent()`` reads the last
  finished roots of an engine.

Nothing is switched on or off: with no profiler running a span costs an
inactive annotation, two clock reads and a dict update.
"""
from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import Callable, Dict, List

import jax
import numpy as np

#: Counter of the bytes an answer copies from the device to the host.
PULL_BYTES = "repro.pull_bytes"

#: Finished roots kept for :func:`recent`; older ones are dropped.
KEEP = 1024


class Root:
    """One finished answer: its span names' self times and its counters.

    All spans opened while the root is open share its ``id``."""

    __slots__ = ("name", "id", "duration_s", "spans", "counts", "_self_ns")

    def __init__(self, name: str, root_id: int) -> None:
        self.name, self.id = name, root_id
        self.duration_s = 0.0
        self.spans: List[str] = []          # closed child spans, in order
        self.counts: Dict[str, int] = {}
        self._self_ns: Dict[str, int] = {}

    def self_s(self, name: str) -> float:
        """Seconds spent in spans called ``name``, less their children."""
        return self._self_ns.get(name, 0) * 1e-9


class _State(threading.local):
    def __init__(self) -> None:
        self.stack: List["span"] = []


_clock = time.perf_counter_ns
_open = _State()
_finished: "collections.deque[Root]" = collections.deque(maxlen=KEEP)
_ids = itertools.count(1)


class span:
    """Context manager: one span called ``name`` under the open root, or a
    new root when none is open.  A span named like the innermost open one
    is that span (re-entry is a no-op), so an entry point that calls
    another of the same engine still makes one root.  As a decorator it
    opens a fresh span around each call."""

    __slots__ = ("name", "root", "child_ns", "_t0", "_note")

    def __init__(self, name: str) -> None:
        self.name = name

    def __call__(self, fn: Callable) -> Callable:
        name = self.name

        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return traced

    def __enter__(self) -> "span":
        stack = _open.stack
        if stack and stack[-1].name == self.name:
            self._note = None
            return self
        self.root = stack[0].root if stack else Root(self.name, next(_ids))
        self.child_ns = 0
        stack.append(self)
        self._note = jax.profiler.TraceAnnotation(self.name)
        self._note.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        if self._note is None:
            return
        dt = _clock() - self._t0
        self._note.__exit__(*exc)
        stack = _open.stack
        stack.pop()
        root = self.root
        selfs = root._self_ns
        selfs[self.name] = selfs.get(self.name, 0) + dt - self.child_ns
        if stack:
            stack[-1].child_ns += dt
            root.spans.append(self.name)
        else:
            root.duration_s = dt * 1e-9
            _finished.append(root)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the open root (none open: no-op)."""
    stack = _open.stack
    if stack:
        counts = stack[0].root.counts
        counts[name] = counts.get(name, 0) + n


def wait(name: str, first) -> None:
    """Inside span ``name``, block until the device has computed ``first``
    (and copied it to the host, where JAX keeps the copy for the pull that
    follows).  This is the block the first ``np.asarray`` of the outputs
    makes; a separate ``jax.block_until_ready`` would add a host wake-up
    per answer."""
    with span(name):
        np.asarray(first)


def pull(name: str, *arrays) -> List:
    """Copy device arrays to the host inside span ``name``, adding their
    bytes to :data:`PULL_BYTES`; returns them as NumPy arrays."""
    with span(name):
        count(PULL_BYTES, sum(int(a.nbytes) for a in arrays))
        return [np.asarray(a) for a in arrays]


def recent(root_name: str, n: int) -> List[Root]:
    """The last ``n`` finished roots called ``root_name``, oldest first
    (fewer when fewer were kept)."""
    if n <= 0:
        return []
    found = [r for r in list(_finished) if r.name == root_name]
    return found[-n:]
