"""Event tracing for the serving stack: Chrome trace-event JSON.

The discrete-event loops in ``repro.serve.engine`` (gang rounds) and
``repro.serve.scheduler`` (continuous batching) feed a
:class:`TraceRecorder` with typed spans and instants — the per-request
lifecycle (enqueue → admit → execute → retire / steal / retry /
failed), per-replica tracks, and fleet-level instants for replica
fail/recover, hot-swap rolls and autoscale decisions. The recorder
exports the Chrome trace-event format (``{"traceEvents": [...]}``),
which Perfetto / ``chrome://tracing`` load directly: one process
("repro.serve"), one thread track per replica plus a "fleet" track for
fleet-scope instants.

Determinism is a contract, not an accident: on the modeled clock every
timestamp derives from the roofline model, the recorder assigns
track ids and sequence numbers in emission order, and ``to_json`` is
canonical (sorted keys, events ordered by ``(ts, tid, seq)``) — two
identical runs produce byte-identical trace files, which the test
suite asserts. Recording never touches the simulated clock, so modeled
benchmark rows are unchanged with tracing on (also asserted).

Span/instant taxonomy (names are the reconciliation contract — the
validator counts them against ``FleetReport``, see
``repro.obs.validate``):

  ============  =====  ========  =======================================
  name          ph     track     meaning
  ============  =====  ========  =======================================
  request       X      replica   one served request: admit -> retire
  round         X      replica   one gang round on one replica
  enqueue       i      replica   router accepted a request into a queue
  reject        i      fleet     admission control rejected a request
  retry         i      fleet     a lost request re-dispatched (budget)
  failed        i      fleet     retry budget exhausted -> failed
  steal         i      replica   thief replica stole a queued request
  fail          i      fleet     a replica failure landed
  recover       i      fleet     a failed replica restored into dispatch
  hot_swap      i      fleet     a replica rolled onto a new artifact
  scale_up      i      fleet     autoscaler spun a replica up
  scale_down    i      fleet     autoscaler drained a replica out
  sweep         X      compile   one compile's DSE resolve (lookups+sweeps)
  measure       X      compile   one plan's wall-clock measurement
  ============  =====  ========  =======================================

The ``compile`` track extends the same timeline down into the compile
phase (PR 9): ``compile_cnn(..., trace=...)`` emits a ``sweep`` span
over its DSE-resolve block and, with ``measure=True``, one ``measure``
span per profiled plan — so one Perfetto view shows where compile time
went before the first request span begins.

Wall-clock spans of real runs
-----------------------------

Everything above runs on the modeled clock. :data:`SPANS`, a
:class:`SpanLog`, records what the process really did, on the host's
``time.perf_counter_ns`` clock (read in :func:`now_ns` and nowhere
else): ``(name, start_ns, end_ns, span_id, parent_id, args)`` tuples in
a bounded buffer that drops its oldest spans when full and counts them.
It is on by default, so it must stay cheap: a span is one tuple
appended, and with the log off a call costs one branch and records
nothing. ``REPRO_SPANS=0`` in the environment at import, or
:func:`set_spans`, switches it off. :meth:`SpanLog.read` returns the
spans and counters; :meth:`SpanLog.export` writes them through a
:class:`TraceRecorder`, one ``wall`` track, for Perfetto.

  ===================  =====  ===============================================
  name                 kind   meaning
  ===================  =====  ===============================================
  cnn.forward          span   one ``CompiledCNN.forward``: batch, images, bytes
  cnn.h2d              span   the host's time in the batch's copy call
  cnn.dispatch         span   the jitted call, up to its return (not-ready)
  cnn.retrace          inst.  + counter: ``jax.jit`` traced the forward
  py.gc                span   one generation-1 or -2 collection
  py.gc.gen0           count  generation-0 collections (counted only)
  conv.kw_fold         count  ``compile_cnn``: a conv group whose column taps
                              ``conv_pipe`` folds into its contraction
  conv.kh_fold         count  ``compile_cnn``: a conv group whose row taps
                              fold into that contraction too
  conv.pool_fused      count  ``compile_cnn``: a conv group whose pool runs in
                              ``conv_pipe``'s epilogue
  conv.halo_in_kernel  count  ``compile_cnn``: a conv group whose zero halo
                              ``conv_pipe`` makes in VMEM, not the wrapper
  ===================  =====  ===============================================

An instant is a span whose end equals its start.
"""
from __future__ import annotations

import gc
import itertools
import json
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional

# Event categories (the "cat" field): filterable lanes in Perfetto.
CAT_REQUEST = "request"        # per-request lifecycle events
CAT_ROUND = "round"            # gang-round execution spans
CAT_FLEET = "fleet"            # fleet mutations (faults, swaps, scaling)
CAT_COMPILE = "compile"        # compile-phase spans (DSE sweep, measure)
CAT_WALL = "wall"              # wall-clock spans of real runs (SpanLog)

FLEET_TRACK = "fleet"          # the non-replica instant track
COMPILE_TRACK = "compile"      # the compile-phase span track
WALL_TRACK = "wall"            # the exported SpanLog's track


class TraceRecorder:
    """Collects typed spans/instants; exports Chrome trace-event JSON.

    All times are in (simulated) seconds; the export converts to the
    format's microseconds. Tracks are named lanes (``"fleet"``,
    ``"replica 0"``, ...) assigned thread ids in first-registration
    order — register tracks up front (the serve loops do) so ids do
    not depend on event order.
    """

    PID = 1

    def __init__(self, process_name: str = "repro.serve"):
        self.process_name = process_name
        self._events: List[dict] = []
        self._tracks: Dict[str, int] = {}
        self._meta: Dict[str, object] = {}
        self._seq = 0

    # -- tracks ------------------------------------------------------------

    def track(self, name: str) -> int:
        """Thread id for a named track (registering it on first use)."""
        if name not in self._tracks:
            self._tracks[name] = len(self._tracks)
        return self._tracks[name]

    # -- emission ----------------------------------------------------------

    def _emit(self, ev: dict) -> None:
        ev["pid"] = self.PID
        ev["seq"] = self._seq
        self._seq += 1
        self._events.append(ev)

    def span(self, name: str, t0: float, t1: float, *,
             track: str, cat: str = CAT_ROUND,
             args: Optional[dict] = None) -> None:
        """A complete event (``ph: "X"``) on ``track``: [t0, t1] seconds."""
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
              "tid": self.track(track)}
        if args:
            ev["args"] = dict(args)
        self._emit(ev)

    def instant(self, name: str, t: float, *,
                track: str = FLEET_TRACK, cat: str = CAT_FLEET,
                args: Optional[dict] = None) -> None:
        """A thread-scoped instant event (``ph: "i"``) at ``t`` seconds."""
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": t * 1e6, "tid": self.track(track)}
        if args:
            ev["args"] = dict(args)
        self._emit(ev)

    def set_meta(self, key: str, value) -> None:
        """Attach run-level metadata (exported under ``otherData``) —
        e.g. the compiled plan provenance and roofline breakdown, so the
        trace records which plans its spans executed."""
        self._meta[key] = value

    # -- counts (reconciliation helpers) -----------------------------------

    def count(self, name: str) -> int:
        """How many events named ``name`` were recorded — the counters
        the validator reconciles against ``FleetReport``."""
        return sum(1 for e in self._events if e["name"] == name)

    def __len__(self) -> int:
        return len(self._events)

    # -- export ------------------------------------------------------------

    def to_chrome(self) -> dict:
        """The trace as a Chrome trace-event document (Perfetto-loadable).

        Events are ordered by ``(ts, tid, seq)`` — per-track timestamps
        are monotone non-decreasing in file order, which the validator
        asserts. Metadata events name the process and every track.
        """
        meta_events = [{"name": "process_name", "ph": "M", "pid": self.PID,
                       "tid": 0, "args": {"name": self.process_name}}]
        for name, tid in sorted(self._tracks.items(), key=lambda kv: kv[1]):
            meta_events.append({"name": "thread_name", "ph": "M",
                                "pid": self.PID, "tid": tid,
                                "args": {"name": name}})
        body = sorted(self._events,
                      key=lambda e: (e["ts"], e["tid"], e["seq"]))
        events = meta_events + [{k: v for k, v in e.items() if k != "seq"}
                                for e in body]
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": dict(self._meta)}

    def to_json(self) -> str:
        """Canonical JSON (sorted keys): byte-identical across identical
        runs on the modeled clock — the determinism contract."""
        return json.dumps(self.to_chrome(), sort_keys=True, indent=1) + "\n"

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_json())
        return path


# -- wall-clock spans --------------------------------------------------------

SPANS_ENV = "REPRO_SPANS"      # "0" at import switches SPANS off
SPAN_CAPACITY = 1 << 16        # spans kept before the oldest are dropped

Args = Optional[tuple]         # flat: ("key", value, "key", value, ...)


def now_ns() -> int:
    """The host clock every wall-clock span reads, in ns."""
    # repro: allow[RPA102] wall-clock spans time the real run path
    return time.perf_counter_ns()


def _as_dict(args: Args) -> Optional[dict]:
    return None if args is None else dict(zip(args[::2], args[1::2]))


class SpanLog:
    """Wall-clock spans and counters of real runs, in a bounded buffer.

    A span is ``(name, start_ns, end_ns, span_id, parent_id, args)``;
    ids count from 1, and a parent id of 0 means none. ``args`` is given
    flat, ``("key", value, ...)``, and read back as a dict: a tuple of
    numbers and strings is one allocation, and the garbage collector
    stops tracking it, so a full buffer adds nothing to a collection's
    work. When ``capacity`` spans are held, each new span drops the
    oldest and ``dropped`` counts it. Counters are plain name -> int.
    """

    def __init__(self, capacity: int = SPAN_CAPACITY, enabled: bool = True):
        self.capacity = capacity
        self.enabled = enabled
        self.dropped = 0
        self.counters: Dict[str, int] = {}
        self._spans: deque = deque(maxlen=capacity)
        self._ids = itertools.count(1)

    def record(self, name: str, t0: int, t1: int, parent: int = 0,
               args: Args = None) -> int:
        """Keep a finished span [t0, t1] (:func:`now_ns` readings);
        returns its id, 0 with the log off."""
        if not self.enabled:
            return 0
        sid = next(self._ids)
        if len(self._spans) == self.capacity:
            self.dropped += 1
        self._spans.append((name, t0, t1, sid, parent, args))
        return sid

    def instant(self, name: str, args: Args = None) -> int:
        """A span whose end is its start."""
        if not self.enabled:
            return 0
        t = now_ns()
        return self.record(name, t, t, 0, args)

    def count(self, name: str) -> None:
        if self.enabled:
            self.counters[name] = self.counters.get(name, 0) + 1

    def read(self) -> Dict[str, Any]:
        """``{"spans": [...], "counters": {...}, "dropped": n}``, spans
        in the order they ended, each with its args as a dict."""
        # copy first: a collection while the dicts are made records a
        # py.gc span into the buffer
        spans = list(self._spans)
        return {"spans": [(n, t0, t1, sid, parent, _as_dict(args))
                          for n, t0, t1, sid, parent, args in spans],
                "counters": dict(self.counters), "dropped": self.dropped}

    def clear(self) -> None:
        self._spans.clear()
        self.counters.clear()
        self.dropped = 0

    def export(self) -> TraceRecorder:
        """The spans as Chrome trace events on the ``wall`` track
        (instants as ``ph: "i"``), in seconds from the first span's
        start, with the span and parent ids in each event's ``args``;
        the counters, the dropped count and the origin
        (``perf_counter_ns``) go under ``otherData``."""
        rec = TraceRecorder("repro.wall")
        got = self.read()
        origin = min((s[1] for s in got["spans"]), default=0)
        for name, t0, t1, sid, parent, args in got["spans"]:
            a = {"id": sid, "parent": parent, **(args or {})}
            if t1 == t0:
                rec.instant(name, (t0 - origin) * 1e-9, track=WALL_TRACK,
                            cat=CAT_WALL, args=a)
            else:
                rec.span(name, (t0 - origin) * 1e-9, (t1 - origin) * 1e-9,
                         track=WALL_TRACK, cat=CAT_WALL, args=a)
        rec.set_meta("wall_origin_ns", origin)
        rec.set_meta("wall_counters", got["counters"])
        rec.set_meta("wall_dropped", got["dropped"])
        return rec


SPANS = SpanLog(enabled=os.environ.get(SPANS_ENV, "1") != "0")

_gc_t0 = 0


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a ``py.gc`` span per generation-1/2
    collection, a ``py.gc.gen0`` count per generation-0 one."""
    global _gc_t0
    gen = info["generation"]
    if gen == 0:
        if phase == "stop":
            SPANS.count("py.gc.gen0")
    elif phase == "start":
        _gc_t0 = now_ns()
    else:
        SPANS.record("py.gc", _gc_t0, now_ns(), 0,
                     ("generation", gen, "collected", info["collected"]))


def set_spans(on: bool) -> bool:
    """Switch :data:`SPANS` (and its garbage-collector hook) on or off;
    returns the previous setting."""
    was = SPANS.enabled
    SPANS.enabled = bool(on)
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    return was


set_spans(SPANS.enabled)
