"""Recorder core of the :mod:`jepsen_tpu.obs` subsystem: a thread-safe,
low-overhead span tracer, a process-wide counters/gauges registry, and
the engine-decision ledger.

Design constraints (ISSUE 2):

- **Cheap enough for hot-ish paths.** One span costs two
  ``time.perf_counter()`` reads, one small dict build, and one
  lock-guarded list append per active sink — single-digit microseconds.
  Instrumentation sits at phase/engine granularity (per check, per
  dispatch group, per run phase), never per history event, so tracer
  overhead on the 100k-op bench rung is bounded by a handful of events
  (asserted under 2% of ``check_s`` in ``tests/test_obs.py``).
  ``JEPSEN_TPU_NO_OBS=1`` disables recording entirely.
- **Thread-safe.** ``core.run`` records from worker threads and the
  competition facade races engines on threads; every recorder mutation
  is lock-guarded and span state lives on the stack (the context
  manager object), not in thread-local registries.
- **Capture isolation.** :func:`capture` registers an extra sink on a
  ``contextvars.ContextVar`` — concurrent captures on different
  threads never see each other's events, while threads *spawned inside*
  a capture can opt in by running under ``contextvars.copy_context()``
  (``core.run`` does this for its workers). Events always also reach
  the process-global recorder, which :mod:`jepsen_tpu.obs.trace`
  exports.
- **Bounded.** Span and ledger stores are capped; drops are themselves
  counted (``obs.dropped.spans`` / ``obs.dropped.ledger``) so a capped
  export is never mistaken for a complete one.
- **Parented.** Every span records ``id``, ``parent`` (the innermost
  open span of the same ``contextvars`` context, 0 at a root) and
  ``root`` in its args, so threads run under ``copy_context()`` nest
  under the span that spawned them and a request's work can be summed
  under its root.
- **On the profiler's clock.** While a ``jax.profiler`` session is
  active, each span also enters a ``TraceAnnotation`` of its name, so
  the program's spans sit on the trace's host plane beside the device
  ops. ``jax`` is never imported here: the bridge looks for it in
  ``sys.modules``.
"""
from __future__ import annotations

import bisect
import contextvars
import itertools
import math
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

_ENABLED = not os.environ.get("JEPSEN_TPU_NO_OBS")

# one process-wide monotonic origin so span timestamps from every
# thread land on one comparable axis (Chrome traces sort by ts)
_T0 = time.perf_counter()

_MAX_SPANS = 100_000
_MAX_LEDGER = 10_000

# Fixed log-spaced histogram buckets shared by EVERY histogram: ten
# buckets per decade (ratio 10^0.1 ~ 1.26) from 1 µs to 1000 s. One
# fixed layout means snapshots merge/difference bucket-by-bucket
# (loadgen's /metrics-delta quantile cross-check depends on that) and
# the quantile interpolation error stays well under the 15% the
# cross-check allows. Values past the last edge land in a +Inf
# overflow bucket; the recorded sum keeps the mean exact regardless.
HIST_EDGES: Tuple[float, ...] = tuple(
    round(10.0 ** (k / 10.0), 12) for k in range(-60, 31))


def _now_us() -> float:
    return (time.perf_counter() - _T0) * 1e6


class Recorder:
    """One sink of spans, counters, gauges, and ledger records. The
    process-global instance backs :func:`jepsen_tpu.obs.trace.export_*`;
    additional instances are created per :func:`capture`."""

    __slots__ = ("_lock", "spans", "counters", "gauges", "ledger",
                 "hists")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.spans: List[Dict[str, Any]] = []
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, Any] = {}
        self.ledger: List[Dict[str, Any]] = []
        self.hists: Dict[str, Dict[str, Any]] = {}

    # -- mutation (all lock-guarded) ------------------------------------
    def add_span(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self.spans) >= _MAX_SPANS:
                self.counters["obs.dropped.spans"] = \
                    self.counters.get("obs.dropped.spans", 0) + 1
                return
            self.spans.append(ev)

    def count(self, name: str, n: float = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value: Any) -> None:
        with self._lock:
            self.gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        """One histogram observation. ``le`` semantics (Prometheus):
        bucket ``i`` counts values ``<= HIST_EDGES[i]``; the trailing
        slot is the +Inf overflow bucket."""
        with self._lock:
            h = self.hists.get(name)
            if h is None:
                h = self.hists[name] = {
                    "count": 0, "sum": 0.0,
                    "counts": [0] * (len(HIST_EDGES) + 1)}
            h["counts"][bisect.bisect_left(HIST_EDGES, value)] += 1
            h["count"] += 1
            h["sum"] += value

    def decide(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            if len(self.ledger) >= _MAX_LEDGER:
                self.counters["obs.dropped.ledger"] = \
                    self.counters.get("obs.dropped.ledger", 0) + 1
                return
            self.ledger.append(rec)

    # -- read side ------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Consistent copy of counters, gauges, histograms, and the
        ledger (spans are exported separately — they can be large)."""
        with self._lock:
            return {"counters": dict(self.counters),
                    "gauges": dict(self.gauges),
                    "histograms": {k: {"count": h["count"],
                                       "sum": h["sum"],
                                       "counts": list(h["counts"])}
                                   for k, h in self.hists.items()},
                    "ledger": [dict(r) for r in self.ledger]}

    def span_events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self.spans]

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.counters.clear()
            self.gauges.clear()
            self.ledger.clear()
            self.hists.clear()


GLOBAL = Recorder()

# extra sinks registered by capture(); a ContextVar (not a thread-local)
# so captures nest and explicit contextvars.copy_context() propagation
# into worker threads works, while unrelated threads stay isolated.
_CAPTURES: "contextvars.ContextVar[Tuple[Recorder, ...]]" = \
    contextvars.ContextVar("jepsen_tpu_obs_captures", default=())


def _sinks() -> Tuple[Recorder, ...]:
    caps = _CAPTURES.get()
    return (GLOBAL,) + caps if caps else (GLOBAL,)


def enabled() -> bool:
    return _ENABLED


# -- spans ---------------------------------------------------------------

_SPAN_IDS = itertools.count(1)

# (id, root) of the innermost open span of this context; (0, 0) outside
# any. A ContextVar for the same reason as _CAPTURES: threads run under
# a copied context nest under the span that spawned them.
_OPEN: "contextvars.ContextVar[Tuple[int, int]]" = \
    contextvars.ContextVar("jepsen_tpu_obs_open", default=(0, 0))

# jax.profiler.TraceAnnotation, looked up once jax has been imported
_ANNOTATION: Any = None


def _annotation(name: str):
    """An entered ``TraceAnnotation`` named ``name`` while a profiler
    session is active, else None (one ``is_enabled`` call)."""
    global _ANNOTATION
    cls = _ANNOTATION
    if cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        cls = _ANNOTATION = TraceAnnotation
    if not cls.is_enabled():
        return None
    ann = cls(name)
    ann.__enter__()
    return ann


class Span:
    """Context manager recording one Chrome-trace ``"X"`` (complete)
    event on exit. ``set(key, value)`` adds args mid-flight (e.g. the
    engine a check ultimately selected). The event's args carry the
    span's ``id``, its ``parent`` and its ``root``."""

    __slots__ = ("name", "cat", "args", "_ts", "_id", "_parent", "_root",
                 "_token", "_ann")

    def __init__(self, name: str, cat: str = "",
                 args: Optional[Dict[str, Any]] = None):
        self.name = name
        self.cat = cat
        self.args = args

    def set(self, key: str, value: Any) -> None:
        if self.args is None:
            self.args = {}
        self.args[key] = value

    def __enter__(self) -> "Span":
        parent, root = _OPEN.get()
        self._id = sid = next(_SPAN_IDS)
        self._parent, self._root = parent, root or sid
        self._token = _OPEN.set((sid, self._root))
        self._ann = _annotation(self.name)
        self._ts = _now_us()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end = _now_us()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        try:
            _OPEN.reset(self._token)
        except ValueError:      # exited in a context that never saw it open
            pass
        args = self.args if self.args is not None else {}
        args.update(id=self._id, parent=self._parent, root=self._root)
        if exc_type is not None:
            args["error"] = exc_type.__name__
        ev: Dict[str, Any] = {
            "name": self.name, "ph": "X", "ts": self._ts,
            "dur": end - self._ts, "pid": os.getpid(),
            "tid": threading.get_ident(), "args": args,
        }
        if self.cat:
            ev["cat"] = self.cat
        for s in _sinks():
            s.add_span(ev)


class _NullSpan:
    __slots__ = ()

    def set(self, key: str, value: Any) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


_NULL_SPAN = _NullSpan()


def span(name: str, cat: str = "", **args: Any):
    """``with obs.span("reach.walk", engine="reach-lockstep"): ...`` —
    nestable, thread-safe; exported as a Chrome/Perfetto trace event."""
    if not _ENABLED:
        return _NULL_SPAN
    return Span(name, cat, args or None)


# -- counters / gauges ---------------------------------------------------

def count(name: str, n: float = 1) -> None:
    """Bump a process-wide (and any captured) counter."""
    if not _ENABLED:
        return
    for s in _sinks():
        s.count(name, n)


def gauge(name: str, value: Any) -> None:
    """Set a last-value-wins gauge (e.g. kernel-cache hit counts)."""
    if not _ENABLED:
        return
    for s in _sinks():
        s.gauge(name, value)


def counters() -> Dict[str, float]:
    """Snapshot of the process-global counters."""
    return GLOBAL.snapshot()["counters"]


def gauges() -> Dict[str, Any]:
    """Snapshot of the process-global gauges (e.g. the streaming
    pipeline's ``prep.wall_s`` / ``prep.hidden_s`` overlap figures)."""
    return GLOBAL.snapshot()["gauges"]


# -- histograms ----------------------------------------------------------

def histogram(name: str, value: float) -> None:
    """Observe ``value`` into the fixed log-spaced histogram ``name``
    (process-wide and any captures). The serving layer feeds these
    with per-request queue-wait / service-time / end-to-end latency
    and per-dispatch-group kernel wall; ``GET /metrics`` exposes them
    as Prometheus ``_bucket``/``_sum``/``_count`` series."""
    if not _ENABLED:
        return
    for s in _sinks():
        s.observe(name, value)


def histograms() -> Dict[str, Dict[str, Any]]:
    """Snapshot of the process-global histograms:
    ``{name: {"count", "sum", "counts"}}`` with ``counts`` the raw
    per-bucket tallies aligned to :data:`HIST_EDGES` plus one +Inf
    overflow slot."""
    return GLOBAL.snapshot()["histograms"]


def hist_merge(a: Optional[Dict[str, Any]],
               b: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Bucket-wise sum of two histogram snapshots (same fixed bucket
    layout, so merging is elementwise)."""
    if a is None or b is None:
        src = a or b or {"count": 0, "sum": 0.0,
                         "counts": [0] * (len(HIST_EDGES) + 1)}
        return {"count": src["count"], "sum": src["sum"],
                "counts": list(src["counts"])}
    return {"count": a["count"] + b["count"],
            "sum": a["sum"] + b["sum"],
            "counts": [x + y for x, y in zip(a["counts"],
                                             b["counts"])]}


def hist_delta(after: Optional[Dict[str, Any]],
               before: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """``after - before`` bucket-wise: the distribution of the
    observations that happened BETWEEN two snapshots of a cumulative
    histogram (both loadgen's /metrics cross-check and the daemon's
    time-series ring difference snapshots this way). Negative cells
    (a reset between snapshots) clamp to zero."""
    if after is None:
        return {"count": 0, "sum": 0.0,
                "counts": [0] * (len(HIST_EDGES) + 1)}
    if before is None:
        return {"count": after["count"], "sum": after["sum"],
                "counts": list(after["counts"])}
    counts = [max(0, x - y) for x, y in zip(after["counts"],
                                            before["counts"])]
    return {"count": sum(counts),
            "sum": max(0.0, after["sum"] - before["sum"]),
            "counts": counts}


def hist_quantile(h: Optional[Dict[str, Any]],
                  q: float) -> Optional[float]:
    """Estimate the ``q``-quantile (0..1) of a histogram snapshot by
    linear interpolation within the bucket holding the target rank.
    None for an empty histogram. The overflow bucket reports the last
    edge (a floor — the true value is larger)."""
    if not h or not h.get("count"):
        return None
    counts = h["counts"]
    target = q * h["count"]
    acc = 0.0
    for i, c in enumerate(counts):
        if not c:
            continue
        acc += c
        if acc >= target:
            if i >= len(HIST_EDGES):            # +Inf overflow
                return HIST_EDGES[-1]
            lo = HIST_EDGES[i - 1] if i > 0 else 0.0
            hi = HIST_EDGES[i]
            frac = (target - (acc - c)) / c
            return lo + (hi - lo) * min(1.0, max(0.0, frac))
    return HIST_EDGES[-1]


def quantile_from_cumulative(pairs: List[Tuple[float, float]],
                             q: float) -> Optional[float]:
    """Quantile from Prometheus-style CUMULATIVE buckets:
    ``pairs = [(le, cumulative_count), ...]`` (any order; +Inf
    allowed). This is the parse-side twin of :func:`hist_quantile` —
    loadgen feeds it the bucket DELTAS of two /metrics scrapes."""
    pairs = sorted((float(le), float(v)) for le, v in pairs)
    if not pairs:
        return None
    total = pairs[-1][1]
    if total <= 0:
        return None
    target = q * total
    prev_le, prev_cum = 0.0, 0.0
    for le, cum in pairs:
        if cum >= target:
            if math.isinf(le):
                return prev_le if prev_le > 0 else None
            width = cum - prev_cum
            frac = ((target - prev_cum) / width) if width > 0 else 1.0
            lo = prev_le if not math.isinf(prev_le) else 0.0
            return lo + (le - lo) * min(1.0, max(0.0, frac))
        prev_le, prev_cum = le, cum
    return pairs[-1][0] if not math.isinf(pairs[-1][0]) else None


def hist_summary(h: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """Headline digest of one histogram snapshot — the shape
    ``bench.py --serve`` and the ``/engine`` dashboard embed."""
    if not h or not h.get("count"):
        return {"count": 0}
    n = h["count"]
    out = {"count": int(n), "sum": round(h["sum"], 6),
           "mean": round(h["sum"] / n, 6)}
    for label, q in (("p50", 0.50), ("p90", 0.90), ("p99", 0.99)):
        v = hist_quantile(h, q)
        out[label] = round(v, 6) if v is not None else None
    return out


# -- engine-decision ledger ---------------------------------------------

def decision(stage: str, event: str, cause: Optional[str] = None,
             **fields: Any) -> None:
    """Append a structured record to the engine-decision ledger:
    ``stage`` (engine or pipeline stage), ``event`` (``"selected"`` /
    ``"fallback"`` / ``"swallowed"`` / ``"route"``), optional ``cause``
    (exception class or reason), plus free-form fields (history
    geometry, elapsed seconds)."""
    if not _ENABLED:
        return
    rec: Dict[str, Any] = {"ts": round(_now_us()), "stage": stage,
                           "event": event}
    if cause is not None:
        rec["cause"] = cause
    rec.update(fields)
    for s in _sinks():
        s.decide(rec)


def engine_selected(stage: str, **fields: Any) -> None:
    """An engine produced the conclusive verdict for a check. Bumps
    ``engine.selected.<stage>`` and appends a ledger record."""
    count(f"engine.selected.{stage}")
    decision(stage, "selected", **fields)


def engine_fallback(stage: str, cause: str, **fields: Any) -> None:
    """A stage was abandoned and the chain moved on. Bumps
    ``engine.fallback.<stage>.<cause>`` (fallback causes keyed by
    exception class and stage) and appends a ledger record."""
    count(f"engine.fallback.{stage}.{cause}")
    decision(stage, "fallback", cause=cause, **fields)


def checker_swallowed(stage: str, cause: str, **fields: Any) -> None:
    """``check_safe`` turned a checker crash into ``"unknown"`` — the
    crash is preserved here (and in the result's ``"traceback"``) so it
    is never silent."""
    count(f"checker.swallowed.{stage}.{cause}")
    decision(stage, "swallowed", cause=cause, **fields)


# -- capture -------------------------------------------------------------

class Capture:
    """Events recorded while a :func:`capture` context is active, plus
    assertion helpers for tests (``selections()`` / ``fallbacks()`` /
    ``swallowed()``)."""

    def __init__(self) -> None:
        self._rec = Recorder()

    # field-specific locked copies — a counters read must not copy a
    # ledger sitting at its 10k cap
    @property
    def spans(self) -> List[Dict[str, Any]]:
        return self._rec.span_events()

    @property
    def counters(self) -> Dict[str, float]:
        with self._rec._lock:
            return dict(self._rec.counters)

    @property
    def gauges(self) -> Dict[str, Any]:
        with self._rec._lock:
            return dict(self._rec.gauges)

    @property
    def histograms(self) -> Dict[str, Dict[str, Any]]:
        with self._rec._lock:
            return {k: {"count": h["count"], "sum": h["sum"],
                        "counts": list(h["counts"])}
                    for k, h in self._rec.hists.items()}

    @property
    def ledger(self) -> List[Dict[str, Any]]:
        with self._rec._lock:
            return [dict(r) for r in self._rec.ledger]

    def _by_event(self, event: str) -> List[Dict[str, Any]]:
        return [r for r in self.ledger if r.get("event") == event]

    def selections(self) -> List[Dict[str, Any]]:
        return self._by_event("selected")

    def fallbacks(self) -> List[Dict[str, Any]]:
        return self._by_event("fallback")

    def swallowed(self) -> List[Dict[str, Any]]:
        return self._by_event("swallowed")

    def summary(self) -> Dict[str, Any]:
        """JSON-serializable counters + gauges + ledger (no spans)."""
        return self._rec.snapshot()


class _CaptureCtx:
    __slots__ = ("_cap", "_token")

    def __init__(self) -> None:
        self._cap = Capture()

    def __enter__(self) -> Capture:
        self._token = _CAPTURES.set(_CAPTURES.get() + (self._cap._rec,))
        return self._cap

    def __exit__(self, exc_type, exc, tb) -> None:
        _CAPTURES.reset(self._token)


def capture() -> _CaptureCtx:
    """``with obs.capture() as cap:`` — everything recorded in this
    context (same thread, or threads run under a copied
    ``contextvars`` context) is ALSO collected into ``cap``, isolated
    from concurrent captures on other threads. Recording into the
    process-global recorder is unaffected."""
    return _CaptureCtx()


def reset() -> None:
    """Clear the process-global recorder (tests and long-lived tools)."""
    GLOBAL.clear()
