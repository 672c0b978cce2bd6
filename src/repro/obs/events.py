"""Layer 2: the executor event log — a structured host-side JSONL recorder.

Everything here runs OUTSIDE the trace. The runner's executor cache and the
``_audit_wrap`` call layer forward events to the module-level ``RECORDER``
(installed via ``recording()`` / ``install``): one ``compile`` event per
top-level executor call that moved ``runner.TRACE_COUNTS`` (executor
family, trace tags, wall seconds, donation tuple, optionally jaxpr const
bytes), one ``cache`` event per executor-cache hit / miss / put / eviction,
one ``phase`` event per benchmark phase (``repro.obs.profile.phase``), and
``metric`` events carrying training-loop scalars (the
``launch.metrics.MetricsLogger`` schema, which is now a shim over this
recorder). ``python -m repro.obs report`` summarizes a log.

Nothing here runs at trace time: ``observed_call`` reads the executors'
own trace marker, ``runner.TRACE_COUNTS``, before and after a call.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from collections import deque
from typing import Optional

# default event-log path (repo-root relative; uncommitted, see .gitignore)
DEFAULT_PATH = "obs_events.jsonl"


class EventRecorder:
    """JSONL event stream + rolling metric aggregates, context-managed.

    ``path=None`` keeps events in ``self.records`` only (tests); a path
    appends JSONL. ``const_bytes=True`` additionally re-traces each compiled
    executor on its recorded operands to log jaxpr const bytes (host
    backends only — donation must be a no-op for the operands to survive).
    """

    def __init__(self, path: Optional[str] = None, *, window: int = 50,
                 const_bytes: bool = False):
        self.path = path
        self.const_bytes = const_bytes
        self.records = []
        self._fh = None
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._fh = open(path, "a")
        self._win = {}
        self._window = window
        self._t0 = time.time()

    def event(self, kind: str, **fields) -> dict:
        rec = {"kind": kind, "t": round(time.time() - self._t0, 3), **fields}
        self.records.append(rec)
        if self._fh:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        return rec

    def metric(self, step: int, **values) -> dict:
        """A training-loop metric event (the MetricsLogger schema plus a
        ``kind`` discriminator); floats also feed the rolling means."""
        floats = {}
        for k, v in values.items():
            v = float(v)
            floats[k] = v
            self._win.setdefault(k, deque(maxlen=self._window)).append(v)
        return self.event("metric", step=step, **floats)

    def mean(self, key: str) -> float:
        buf = self._win.get(key)
        return sum(buf) / len(buf) if buf else float("nan")

    def close(self):
        if self._fh:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


# the installed recorder; ``emit`` is a no-op while this is None, so the
# executor hooks cost one None-check when nothing is recording
RECORDER: Optional[EventRecorder] = None


def install(recorder: EventRecorder) -> EventRecorder:
    global RECORDER
    RECORDER = recorder
    return recorder


def uninstall() -> None:
    global RECORDER
    RECORDER = None


@contextlib.contextmanager
def recording(path: Optional[str] = None, **kwargs):
    """Install a fresh ``EventRecorder`` for the block and close it after."""
    rec = EventRecorder(path, **kwargs)
    install(rec)
    try:
        yield rec
    finally:
        uninstall()
        rec.close()


def emit(kind: str, **fields) -> None:
    """Forward one event to the installed recorder (no-op when none is)."""
    if RECORDER is not None:
        RECORDER.event(kind, **fields)


def _key_repr(key, limit: int = 200) -> str:
    s = repr(key)
    return s if len(s) <= limit else s[:limit] + "..."


def _donate_of(key):
    """The named donate tuple threaded through an executor cache key (R4):
    the last all-int tuple element, or None for undonated executors."""
    if isinstance(key, tuple):
        for el in reversed(key):
            if (isinstance(el, tuple) and el
                    and all(isinstance(i, int) for i in el)):
                return list(el)
    return None


def observed_call(key, fn, args, kwargs):
    """Run one concrete top-level executor call under the recorder.

    Snapshots ``runner.TRACE_COUNTS`` around the call; when it moved, the
    call paid a (re)trace and a ``compile`` event is
    emitted with the family, trace tags, wall seconds, and donation tuple
    (plus jaxpr const bytes when the recorder opted in).
    """
    from repro.core import runner

    before = dict(runner.TRACE_COUNTS)
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    deltas = runner.trace_deltas(before)
    if deltas:
        family = key[0] if isinstance(key, tuple) and key else str(key)
        fields = dict(
            family=family,
            cache_key=_key_repr(key),
            traces=sum(deltas.values()),
            trace_tags=sorted(deltas),
            compile_s=round(wall, 6),
            donate=_donate_of(key),
        )
        if RECORDER is not None and RECORDER.const_bytes:
            try:
                from repro.analysis import jaxpr_audit

                fields["const_bytes"] = jaxpr_audit.audit_record(
                    fn, args, kwargs)["const_bytes"]
            except Exception as e:  # noqa: BLE001 — best-effort enrichment
                fields["const_bytes_error"] = f"{type(e).__name__}: {e}"
        emit("compile", **fields)
    return out
