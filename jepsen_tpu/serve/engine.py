"""The dispatcher: long-lived lane threads between the admission
queue and the device engines.

The dispatcher runs N *lanes* (default 1), one thread per device or
device group: the coalescer places each ready group onto a lane
(round-robin, least-loaded tie-break — ``serve/coalesce.py``), so one
daemon saturates a multi-chip mesh instead of serializing every group
through one consumer. Per-lane state is isolated: each lane owns its
own circuit breaker (a poisoned lane degrades to host-side serving
alone; its siblings keep the device path) and its own device-ran
attribution flag, so ``serve.device_s`` + ``serve.pad_waste_s`` ==
dispatch wall holds per lane and in the per-lane
``serve.lane.<k>.{device_s,pad_waste_s}`` sums.

Each lane iteration takes ONE coalesced dispatch group from the queue
and runs it through the same checker chains the CLI uses — so daemon
verdicts are the standalone verdicts:

- a group of one goes through :func:`facade.auto_check_packed` (the
  single-history auto chain, abortable through the segmented walk);
- a group of many goes through :func:`facade.auto_check_many_packed`,
  whose first route is the streaming lockstep batch scheduler
  (``reach._stage_lockstep``) — the admission coalescer
  sized the group with the same ``plan_buckets`` packer, so the
  engine-side re-plan reproduces the group geometry.

Because the thread — and the process — lives across requests, the
engine-side caches stay hot: compiled kernel geometries (jax in-proc
+ persistent compilation cache), the memo/disk-memo tiers, and the
device-resident operand cache (``transfer.cached_put``). That is the
entire point of the daemon: request N+1 pays marshalling, not
compilation.

Deadlines and cancellation compose into the chain's ``should_abort``
hook: the group aborts (cleanly, at a segment boundary) once EVERY
live member is expired or cancelled; an individual member whose
deadline passes mid-walk keeps the group running for its co-tenants
but reports ``timeout`` itself. A dispatch exception never kills the
daemon — it enters the recovery ladder (``serve/recovery.py``):
deterministic bounded-backoff retry of the whole group, then group
bisection to corner a poison member (quarantined with a structured
error; the innocent majority completes), with a host-side rescue
before any quarantine. Repeated device-path failures open a circuit
breaker that routes groups to the host checkers (verdicts identical,
slower) until a half-open probe heals it; a dispatch hung past its
wall-clock cap aborts via the same ``should_abort`` composition and
its survivors requeue. Every rung lands in the obs ledger
(``serve-dispatch`` / ``serve-retry`` / ``serve-quarantine`` /
``serve-breaker`` / ``serve-hang``).
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jepsen_tpu import obs
from jepsen_tpu.checkers import dispatch_core
from jepsen_tpu.serve import faults, recovery
from jepsen_tpu.serve import request as rq
from jepsen_tpu.serve.coalesce import AdmissionQueue

log = logging.getLogger("jepsen.serve")


class _StagedDispatch:
    """One serve group staged-but-uncollected on its lane: the engine
    launch is queued on device (``handle`` — a
    :class:`reach.StagedMany`), per-request dispatch bookkeeping is
    done, and the queue slot is still held (released by
    :meth:`Dispatcher._collect_one` after publish, so the drain
    contract is unchanged). ``cap_recs`` carries the obs ledger
    records the stage produced, merged with the collect's capture
    into every member's stitched trace."""

    __slots__ = ("batch", "lane_idx", "kw", "hang", "t0", "pad",
                 "n_real", "handle", "cap_recs")

    def __init__(self, batch, lane_idx, kw, hang, t0, pad, n_real,
                 handle, cap_recs):
        self.batch = batch
        self.lane_idx = lane_idx
        self.kw = kw
        self.hang = hang
        self.t0 = t0
        self.pad = pad
        self.n_real = n_real
        self.handle = handle
        self.cap_recs = cap_recs

    def ready(self) -> bool:
        return self.handle.ready()


def _profiler_start(path: str) -> None:
    """Module-level indirection so tests can stub the profiler."""
    import jax
    jax.profiler.start_trace(path)


def _profiler_stop() -> None:
    import jax
    jax.profiler.stop_trace()


class _TimeSeriesRing:
    """Rolling in-memory time series of serving health: one point per
    completed dispatch — req/s since the previous point, p50/p99 over
    the e2e-latency histogram delta, queue depth, and in-flight lanes.
    Bounded (default 256 points ~ the last few minutes under load);
    serialized into ``stats.json`` so the ``/engine`` dashboard can
    sparkline a daemon it does not share a process with."""

    def __init__(self, cap: int = 256) -> None:
        self._lock = threading.Lock()
        self._points: "deque[Dict[str, Any]]" = deque(maxlen=cap)
        self._prev_ts: Optional[float] = None
        self._prev_done: float = 0.0
        self._prev_hist: Optional[Dict[str, Any]] = None

    def sample(self, queue: AdmissionQueue,
               snap: Optional[Dict[str, Any]] = None) -> None:
        # `snap` shares one Recorder.snapshot() per dispatch between
        # the ring and the stats file — snapshot deep-copies the
        # (up-to-10k-record) ledger under the global obs lock, so
        # taking it once per loop iteration matters
        if snap is None:
            snap = obs.core.GLOBAL.snapshot()
        now = time.monotonic()
        done = snap["counters"].get("serve.completed", 0.0)
        hist = snap["histograms"].get("serve.e2e_s")
        depth = queue.depth()
        lanes = sum(queue.inflight().values())
        with self._lock:
            dt = (now - self._prev_ts) if self._prev_ts is not None \
                else None
            delta = obs.hist_delta(hist, self._prev_hist)
            p50 = obs.hist_quantile(delta, 0.50)
            p99 = obs.hist_quantile(delta, 0.99)
            point = {
                "ts": round(time.time(), 3),
                "req_s": (round((done - self._prev_done) / dt, 3)
                          if dt and dt > 0 else None),
                "p50_s": round(p50, 6) if p50 is not None else None,
                "p99_s": round(p99, 6) if p99 is not None else None,
                "depth": depth,
                "inflight": lanes,
            }
            self._points.append(point)
            self._prev_ts = now
            self._prev_done = done
            self._prev_hist = hist

    def points(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(p) for p in self._points]


class _Lane:
    """One dispatch lane's isolated state. ``breaker`` is this lane's
    own circuit breaker (cloned from the prototype's policy): device
    failures on lane k open lane k's breaker only, so a poisoned lane
    degrades to host-side serving while siblings keep the device
    path. ``device_ran`` is the per-dispatch attribution flag — only
    ever touched by this lane's own thread, no lock."""

    def __init__(self, idx: int,
                 breaker: recovery.CircuitBreaker) -> None:
        self.idx = idx
        self.breaker = breaker
        self.device_ran = False
        self.thread: Optional[threading.Thread] = None
        # pipelined dispatch state (only ever touched by this lane's
        # own thread, no lock): attr_mark is the attribution clock —
        # the end of this lane's last collected interval, so a group
        # whose stage→collect wall overlaps a predecessor books only
        # the un-attributed slice (device_s + pad_waste_s keeps
        # partitioning the lane's busy wall exactly; the overlapped
        # remainder is the pipeline win, counted pipeline.overlap_s)
        self.attr_mark = 0.0
        self.window_peak = 0


class Dispatcher:
    """Owns the dispatch thread. ``start()``/``stop()`` bracket the
    daemon's life; ``drain()`` waits for the queue to empty (tests,
    graceful shutdown)."""

    def __init__(self, queue: AdmissionQueue, registry: "rq.Registry",
                 *, engine_kw: Optional[Dict[str, Any]] = None,
                 store_root: Optional[str] = None,
                 persist: bool = False,
                 retry_policy: Optional[recovery.RetryPolicy] = None,
                 breaker: Optional[recovery.CircuitBreaker] = None,
                 dispatch_deadline_s: Optional[float] = None,
                 journal: Optional[Any] = None,
                 lanes: int = 1) -> None:
        self.queue = queue
        self.registry = registry
        self.engine_kw = dict(engine_kw or {})
        self.store_root = store_root
        self.persist = persist and store_root is not None
        # recovery discipline (serve/recovery.py): deterministic
        # bounded retry + bisect quarantine, the device-path circuit
        # breaker, and the hung-dispatch wall-clock cap past which the
        # group's should_abort fires and survivors requeue
        self.retry = retry_policy or recovery.RetryPolicy()
        # per-lane breaker isolation: the passed breaker (or a fresh
        # default) becomes lane 0's, and each further lane gets its
        # own clone of the same policy — `self.breaker` stays the
        # lane-0 alias for single-lane callers and existing tests
        proto = breaker or recovery.CircuitBreaker()
        self.lanes_n = max(1, int(lanes))
        self._lanes = [_Lane(0, proto)]
        for i in range(1, self.lanes_n):
            self._lanes.append(_Lane(i, recovery.CircuitBreaker(
                threshold=proto.threshold,
                cooldown_s=proto.cooldown_s)))
        self.breaker = proto
        self.dispatch_deadline_s = dispatch_deadline_s
        self.journal = journal          # durable WAL (set by Daemon)
        self.sessions = None            # SessionRegistry (set by Daemon)
        # pod mode: device seconds this rank spends are mirrored into
        # dist.device_s so per-host spend reconciles across the pod's
        # ranks (serve.device_s stays the daemon-local attribution)
        try:
            from jepsen_tpu.parallel import distributed
            self._n_ranks = distributed.process_info()[1]
        # jtlint: ok fallback — capability probe: no jax on the protocol-only path, single-process attribution
        except Exception:                               # noqa: BLE001
            self._n_ranks = 1
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.dispatch_counts: Dict[str, int] = {}
        self._counts_lock = threading.Lock()
        # max staged-window depth seen on any lane (pipeline evidence
        # for /stats and the CI pipeline-smoke gate)
        self._inflight_peak = 0
        self.ring = _TimeSeriesRing()
        # on-demand profiling (POST /profile): arm -> the next N
        # dispatches run under jax.profiler.trace, capture persisted
        # under the store root
        self._profile_lock = threading.Lock()
        self._profile_left = 0
        self._profile_dir: Optional[str] = None
        self._profile_active = False
        queue.on_timeout = self._finish_timeout_queued

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "Dispatcher":
        # warm the persistent caches once, before the first request
        from jepsen_tpu.checkers import reach
        reach._ensure_persistent_caches()
        obs.gauge("serve.lanes", self.lanes_n)
        for lane in self._lanes:
            t = threading.Thread(target=self._loop, args=(lane,),
                                 name=f"serve-dispatch-{lane.idx}",
                                 daemon=True)
            lane.thread = t
            t.start()
        # lane 0's thread doubles as the "is the dispatcher running"
        # handle (drain() and older callers check it)
        self._thread = self._lanes[0].thread
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        end = time.monotonic() + timeout
        for lane in self._lanes:
            t = lane.thread
            if t is not None:
                t.join(max(0.1, end - time.monotonic()))
        # flush a still-open profiler capture: an armed profile that
        # never saw enough dispatches must not leave the trace
        # recording (and its promised capture dir empty) forever
        self._profile_force_stop()

    def drain(self, timeout: float = 60.0) -> bool:
        """Wait until no request is queued or walking. Judged from
        the QUEUE's state alone: a batch moves queued → in-flight
        atomically under the queue lock inside ``next_batch`` and
        leaves in-flight only in ``mark_done`` (after its results
        published), so depth==0 ∧ inflight=={} has no window where a
        batch is about to dispatch — a dispatcher-side idle flag
        would."""
        if self._thread is None:        # never started: nothing will
            return self.queue.depth() == 0  # ever drain the queue
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if self.queue.depth() == 0 and not self.queue.inflight():
                return True
            time.sleep(0.01)
        return False

    # -- the loop --------------------------------------------------------
    def _loop(self, lane: "_Lane") -> None:
        """The lane thread: a bounded window of K staged groups in
        flight (ISSUE 20 tentpole). While group k's walk runs on
        device, this thread stages group k+1 (host pack + puts +
        kernel launch via :meth:`_stage`) and collects any READY
        predecessors; a full window blocks on the oldest group's
        collect (counted ``pipeline.stall_s`` — the host running
        ahead of the device). Batches the stage probe declines
        (sessions, txn, singletons, breaker-open, ineligible engine
        routes) drain the window first — publish order stays FIFO —
        then run the unchanged blocking path. K=1
        (``JEPSEN_TPU_NO_PIPELINE=1``) never stages: every iteration
        is the historical pull→dispatch→mark_done loop, bit-identical
        verdicts AND accounting."""
        window: deque = deque()
        while not self._stop.is_set():
            k = dispatch_core.pipeline_k(
                "serve", default=dispatch_core.SERVE_PIPE_K)
            batch = None
            if len(window) < k:
                # with staged work pending, poll fast: a short pull
                # timeout keeps ready predecessors draining even when
                # the queue is idle
                batch = self.queue.next_batch(
                    timeout=(0.01 if window else 0.1), lane=lane.idx)
            if batch:
                self._profile_maybe_start()
                staged = None
                if k > 1:
                    try:
                        staged = self._stage(batch, lane)
                    except Exception as e:              # noqa: BLE001
                        # jtlint: ok fallback — a stage-probe crash
                        # must not strand the batch: the blocking
                        # path below redoes it from scratch
                        log.warning("stage probe crashed: %r", e,
                                    exc_info=e)
                        obs.count("pipeline.stage_error")
                        staged = None
                if staged is not None:
                    obs.count("pipeline.staged")
                    window.append(staged)
                    if len(window) > lane.window_peak:
                        lane.window_peak = len(window)
                        obs.gauge(
                            f"pipeline.lane.{lane.idx}.inflight_peak",
                            lane.window_peak)
                        self._note_inflight_peak(lane.window_peak)
                    # collect ready predecessors without blocking the
                    # next stage
                    while window and window[0].ready():
                        self._collect_one(window.popleft(), lane)
                    continue
                # not stageable: preserve FIFO publish order — drain
                # the window, then run the blocking path
                while window:
                    self._collect_one(window.popleft(), lane)
                self._run_blocking(batch, lane)
                continue
            if window:
                stalled = len(window) >= k
                t_w = time.monotonic()
                self._collect_one(window.popleft(), lane)
                if stalled:
                    obs.count("pipeline.stall_s",
                              time.monotonic() - t_w)
        # shutdown: collect everything still in flight so no request
        # is stranded un-published
        while window:
            self._collect_one(window.popleft(), lane)

    def _note_inflight_peak(self, peak: int) -> None:
        with self._counts_lock:
            if peak > self._inflight_peak:
                self._inflight_peak = peak
                obs.gauge("pipeline.inflight_peak", peak)

    def _run_blocking(self, batch: List["rq.CheckRequest"],
                      lane: "_Lane") -> None:
        """One blocking dispatch iteration — the pre-pipeline loop
        body, unchanged: dispatch, last-resort containment, queue
        release + stats sampling."""
        try:
            self._dispatch(batch, lane)
        except Exception as e:                          # noqa: BLE001
            # LAST-resort containment: the recovery ladder inside
            # _dispatch handles engine failures; anything escaping
            # it (bookkeeping bugs, injected tick faults) must not
            # kill the lane thread or strand the batch
            log.error("dispatch iteration crashed: %r", e,
                      exc_info=e)
            obs.engine_fallback("serve-dispatch",
                                type(e).__name__,
                                lanes=len(batch), iteration=True)
            now = time.monotonic()
            for r in batch:
                if not r.terminal:
                    self._finish(r, {"valid": "unknown",
                                     "error": f"{type(e).__name__}"
                                              f": {e}"},
                                 0.0, now)
        finally:
            self.queue.mark_done(batch, lane=lane.idx)
            obs.gauge("serve.inflight", 0)
            self._profile_maybe_stop()
            snap = obs.core.GLOBAL.snapshot()
            self.ring.sample(self.queue, snap)
            self._write_stats_file(snap)

    def _collect_one(self, staged: "_StagedDispatch",
                     lane: "_Lane") -> None:
        """Collect + publish one staged group, releasing its queue
        slot only AFTER its results land (the drain contract:
        depth==0 ∧ inflight=={} still means every verdict is
        published)."""
        batch = staged.batch
        try:
            self._collect_staged(staged, lane)
        except Exception as e:                          # noqa: BLE001
            # the same last-resort containment as the blocking loop
            log.error("staged collect crashed: %r", e, exc_info=e)
            obs.engine_fallback("serve-dispatch", type(e).__name__,
                                lanes=len(batch), iteration=True)
            now = time.monotonic()
            for r in batch:
                if not r.terminal:
                    self._finish(r, {"valid": "unknown",
                                     "error": f"{type(e).__name__}"
                                              f": {e}"},
                                 0.0, now)
        finally:
            self.queue.mark_done(batch, lane=lane.idx)
            obs.gauge("serve.inflight", 0)
            self._profile_maybe_stop()
            snap = obs.core.GLOBAL.snapshot()
            self.ring.sample(self.queue, snap)
            self._write_stats_file(snap)

    # -- on-demand profiling ---------------------------------------------
    def arm_profile(self, dispatches: int) -> str:
        """Arm ``jax.profiler.trace`` around the next N dispatches;
        the capture persists under ``<store-root>/serve/profile-<ts>/``.
        Raises RuntimeError when already armed or without a store
        root (the capture needs somewhere durable to land)."""
        if self.store_root is None:
            raise RuntimeError("profiling needs a store root")
        from jepsen_tpu import store
        with self._profile_lock:
            if self._profile_left > 0 or self._profile_active:
                raise RuntimeError(
                    f"profile already armed "
                    f"({self._profile_left} dispatches left)")
            d = store.serve_profile_dir(self.store_root)
            self._profile_dir = d
            self._profile_left = int(dispatches)
            return d

    def profile_state(self) -> Dict[str, Any]:
        with self._profile_lock:
            return {"armed": int(self._profile_left),
                    "active": bool(self._profile_active),
                    "dir": self._profile_dir}

    def _profile_maybe_start(self) -> None:
        with self._profile_lock:
            if self._profile_left <= 0 or self._profile_active:
                return
            path = self._profile_dir
            try:
                _profiler_start(path)
                self._profile_active = True
                obs.decision("serve-profile", "route",
                             cause="start", dir=path,
                             dispatches=self._profile_left)
            except Exception as e:                      # noqa: BLE001
                log.warning("profiler start failed: %s", e)
                obs.engine_fallback("serve-profile",
                                    type(e).__name__)
                self._profile_left = 0

    def _profile_maybe_stop(self) -> None:
        with self._profile_lock:
            if not self._profile_active:
                return
            self._profile_left -= 1
            if self._profile_left > 0:
                return
            self._profile_stop_locked()

    def _profile_force_stop(self) -> None:
        """Stop and flush an active capture regardless of how many
        armed dispatches remain (daemon shutdown)."""
        with self._profile_lock:
            if self._profile_active:
                self._profile_stop_locked()
            self._profile_left = 0

    def _profile_stop_locked(self) -> None:
        try:
            _profiler_stop()
            obs.count("serve.profile.captures")
        except Exception as e:                          # noqa: BLE001
            log.warning("profiler stop failed: %s", e)
            obs.engine_fallback("serve-profile", type(e).__name__)
        self._profile_active = False
        self._profile_left = 0

    # -- engine attempts (the recovery ladder's rungs) -------------------
    @staticmethod
    def _is_txn(model) -> bool:
        from jepsen_tpu.txn.ops import ListAppend
        return isinstance(model, ListAppend)

    def _padded_list(self, batch: List["rq.CheckRequest"]):
        """Quantize the lane count to a power of two by replicating
        the LONGEST member (its verdict is recomputed and discarded;
        padding with the longest keeps the group's padded step count
        unchanged): a serving daemon sees every group width 1..group
        over its life, and each distinct H is a distinct compiled
        kernel geometry — the pad bounds that churn to log2(group)
        geometries a warmup can prime. JEPSEN_TPU_SERVE_NO_PAD=1
        dispatches raw widths. Transactional groups never pad (the
        txn closure kernel pads its own geometry internally)."""
        packed_list = [r.packed for r in batch]
        pad = self._pad_count(len(batch), self._is_txn(batch[0].model))
        if pad > 0:
            longest = max(packed_list, key=lambda p: p.n)
            packed_list = packed_list + [longest] * pad
        return packed_list, pad

    def _pad_count(self, n_real: int, is_txn: bool) -> int:
        if n_real <= 1 or is_txn \
                or os.environ.get("JEPSEN_TPU_SERVE_NO_PAD"):
            return 0
        Hq = 1 << (n_real - 1).bit_length()
        # never pad past the configured group width: the engine-side
        # re-plan splits oversized groups, which would both defeat the
        # pad and break the admission/engine plan agreement
        cap = int(self.engine_kw.get("group") or 0) or 32
        Hq = min(Hq, max(cap, n_real))
        return max(0, Hq - n_real)

    def _run_engine(self, batch: List["rq.CheckRequest"],
                    kw: Dict[str, Any], lane: "_Lane",
                    feed_breaker: bool = True) -> List[Dict[str, Any]]:
        """ONE engine attempt for the (sub)group: consult the LANE's
        circuit breaker for the route, run it, feed the outcome back.
        Raises on failure — recovery policy lives in
        :meth:`_run_recover`.

        ``feed_breaker=False`` (the bisect hunt's sub-attempts) still
        records SUCCESSES (they are honest evidence of device health)
        but not failures: one poison request failing its way down a
        bisect ladder is log2(n) failures from a single bad REQUEST,
        and must not open a breaker that speaks for the DEVICE."""
        from jepsen_tpu.checkers import facade
        tenants = [r.tenant for r in batch]
        # the self-nemesis "dispatch" point models a poison request
        # that crashes the checker on EVERY route; "device" models a
        # device-path outage (the breaker's food)
        faults.fire("dispatch", tenants=tenants)
        if lane.breaker.route() == "host":
            obs.count("serve.breaker.degraded_dispatches")
            obs.decision("serve-breaker", "route", cause="host",
                         lanes=len(batch), lane=lane.idx)
            return self._run_host(batch, kw, fire_point=False)
        req0 = batch[0]
        try:
            faults.fire("device", tenants=tenants)
            # attribution flag: some device work ran this dispatch
            # iteration (even a failed attempt spent device time)
            lane.device_ran = True
            with obs.span("serve.dispatch",
                          model=req0.model_name, lanes=len(batch),
                          reqs=[r.id for r in batch]):
                if self._is_txn(req0.model):
                    # one txn chain per member: host dependency
                    # inference is per-history; the closure kernel
                    # geometry is shared across members via its
                    # power-of-two pad + jit cache
                    results = [facade.auto_check_txn(
                        list(r.history), kw) for r in batch]
                elif len(batch) == 1:
                    results = [facade.auto_check_packed(
                        req0.model, req0.packed, kw)]
                else:
                    packed_list, pad = self._padded_list(batch)
                    if pad:
                        obs.count("serve.pad_lanes", pad)
                    results = facade.auto_check_many_packed(
                        req0.model, packed_list, kw)[:len(batch)]
        except Exception:
            if feed_breaker:
                lane.breaker.record_failure()
            raise
        lane.breaker.record_success()
        return results

    def _run_host(self, batch: List["rq.CheckRequest"],
                  kw: Dict[str, Any],
                  fire_point: bool = True) -> List[Dict[str, Any]]:
        """The degraded route: host-side checkers, per member —
        verdicts identical to the device chain (the Python WGL oracle
        / forced-host txn closure are the same reference the engines
        are differentially tested against), just slower. Used while
        the breaker is open and as the singleton quarantine rescue."""
        from jepsen_tpu.checkers import facade, wgl_ref
        if fire_point:
            faults.fire("dispatch",
                        tenants=[r.tenant for r in batch])
        req0 = batch[0]
        out = []
        with obs.span("serve.dispatch-host",
                      model=req0.model_name, lanes=len(batch),
                      reqs=[r.id for r in batch]):
            for r in batch:
                if self._is_txn(r.model):
                    res = facade.auto_check_txn(
                        list(r.history), dict(kw, force_host=True))
                else:
                    res = wgl_ref.check_packed(
                        r.model, r.packed,
                        **facade._engine_kw(kw, facade._WGL_KW))
                    res["engine"] = res.get("engine", "wgl-cpu")
                res["degraded"] = True
                out.append(res)
        return out

    def _run_recover(self, batch: List["rq.CheckRequest"],
                     kw: Dict[str, Any],
                     retries_left: int, lane: "_Lane",
                     top_level: bool = True) -> List[Dict[str, Any]]:
        """The recovery ladder: attempt → deterministic bounded-backoff
        retry → group bisect to corner the poison member → host-side
        rescue → quarantine. Innocent members of a poisoned group
        complete; only the member that fails ALONE (on both routes) is
        quarantined, with a structured error and an obs record."""
        attempt = 0
        err: Optional[Exception] = None
        while True:
            try:
                return self._run_engine(batch, kw, lane,
                                        feed_breaker=top_level)
            except Exception as e:                      # noqa: BLE001
                err = e
                log.warning("serve dispatch failed (lanes=%d, "
                            "attempt=%d): %r", len(batch), attempt, e,
                            exc_info=e)
                obs.engine_fallback("serve-dispatch",
                                    type(e).__name__,
                                    lanes=len(batch), attempt=attempt)
            if self._stop.is_set():
                return [{"valid": "unknown",
                         "error": f"{type(err).__name__}: {err}"}
                        for _ in batch]
            if retries_left <= 0:
                break
            retries_left -= 1
            obs.count("serve.retry.attempts")
            time.sleep(self.retry.delay(attempt))
            attempt += 1
        if len(batch) > 1:
            # isolate the poison: halves get one attempt each and
            # bisect further on failure — O(log n) extra dispatches
            # to corner one bad member while the rest complete
            obs.count("serve.retry.bisects")
            obs.decision("serve-retry", "bisect", lanes=len(batch),
                         cause=type(err).__name__)
            lo, hi = recovery.bisect(batch)
            return self._run_recover(lo, kw, 0, lane,
                                     top_level=False) \
                + self._run_recover(hi, kw, 0, lane,
                                    top_level=False)
        # a singleton that failed its attempts: one last host-side
        # rescue (device flakiness must not quarantine an innocent
        # request), then quarantine with a structured error
        req = batch[0]
        try:
            obs.decision("serve-retry", "host-rescue", id=req.id)
            return self._run_host(batch, kw)
        except Exception as e:                          # noqa: BLE001
            obs.count("serve.quarantined")
            obs.engine_fallback("serve-quarantine", type(e).__name__,
                                id=req.id, tenant=req.tenant,
                                ops=int(req.n_ops))
            log.warning("quarantining request %s: %r", req.id, e)
            return [{"valid": "unknown", "quarantined": True,
                     "cause": "quarantined",
                     "error": f"{type(e).__name__}: {e}"}]

    def _session_abort(self, t0: float):
        """The session advance's ``should_abort`` hook: the dispatch
        deadline applied to a streaming block. Composed into the
        session's engine steps (``session._advance_engine`` polls it
        between feed/advance/probe), so a hung advance aborts and the
        session takes its ordinary permanent host fallback instead of
        wedging the lane forever. Returns None when no deadline is
        configured (the hook costs a closure per block otherwise)."""
        deadline_s = self.dispatch_deadline_s
        if deadline_s is None:
            return None
        fired = [False]

        def _aborted() -> bool:
            if self._stop.is_set():
                return True
            if time.monotonic() - t0 > deadline_s:
                if not fired[0]:
                    fired[0] = True
                    obs.engine_fallback("serve-hang",
                                        "DispatchDeadline",
                                        session=True,
                                        deadline_s=deadline_s)
                return True
            return False
        return _aborted

    def _dispatch_session(self, batch: List["rq.CheckRequest"],
                          lane: "_Lane") -> None:
        """Session blocks: advance the carried frontier through each
        append (seq order — the coalescer sorted the group), resolve
        the close. No recovery ladder, no breaker, no lane pad: the
        session owns its own fallback contract (exactly one
        ``session-advance`` obs fallback → host monitor), so a block
        that fails here still produces its verdict — host-side. No
        device-time attribution either: the advance wall gets its own
        counter so serve.device_s stays the one-shot walks' number."""
        from jepsen_tpu.serve.session import SessionClosed
        req0 = batch[0]
        sess = req0.session
        sig = f"session/{req0.model_name}/A{len(batch)}"
        with self._counts_lock:
            self.dispatch_counts[sig] = \
                self.dispatch_counts.get(sig, 0) + 1
        obs.count("serve.dispatched", len(batch))
        obs.count(f"serve.lane.{lane.idx}.dispatched")
        obs.gauge("serve.inflight", len(batch))
        t0 = time.monotonic()
        for r in batch:
            r.t_dispatch = time.monotonic()
            obs.histogram("serve.queue_wait_s",
                          max(0.0, (r.t_coalesce or t0) - r.t_submit))
            self.registry.ledger_record(
                r.tenant, "dispatched", id=r.id, group=len(batch),
                ops=int(r.n_ops), session=sess.id, kind=r.kind)
            with obs.capture() as cap:
                try:
                    if r.kind == "session-close":
                        res = sess.close()
                        if self.sessions is not None:
                            self.sessions.mark_closed(sess)
                        if self.journal is not None:
                            self.journal.session_close_marker(
                                sess.id, res)
                    else:
                        res = sess.advance_block(
                            list(r.history), seq=r.seq,
                            should_abort=self._session_abort(
                                r.t_dispatch))
                # jtlint: ok fallback — append/close client race: the member gets a 'closed' verdict
                except SessionClosed as e:
                    res = {"valid": "unknown", "cause": "closed",
                           "error": str(e)}
                except Exception as e:                  # noqa: BLE001
                    # the session's own ladder should have contained
                    # this; a residual crash is recorded, never fatal
                    log.warning("session block %s crashed: %r",
                                r.id, e, exc_info=e)
                    obs.engine_fallback("serve-dispatch",
                                        type(e).__name__,
                                        session=sess.id, id=r.id)
                    if r.kind == "session-close" and not sess.closed:
                        # a close that crashed must not wedge the
                        # session: clearing the in-flight flag lets
                        # the client retry (appends stay refused only
                        # while a close is genuinely pending)
                        sess.closing = False
                    res = {"valid": "unknown",
                           "error": f"{type(e).__name__}: {e}"}
            now = time.monotonic()
            r.t_collect = now
            r.stitch([{"ts": round(time.time(), 6),
                       "stage": "session-advance", "event": "advance",
                       "session": sess.id, "seq": r.seq,
                       "wall_s": round(now - r.t_dispatch, 6)}]
                     + [rec for rec in cap.ledger
                        if rec.get("event") in ("fallback", "route",
                                                "selected")])
            for rec in cap.ledger:
                if rec.get("event") == "fallback":
                    self.registry.ledger_record(
                        r.tenant, "engine-fallback", id=r.id,
                        stage=rec.get("stage"), cause=rec.get("cause"))
            obs.histogram("serve.session.append_s", now - r.t_submit)
            self._finish(r, res, now - r.t_dispatch, now)
        obs.count("serve.session.advance_wall_s",
                  time.monotonic() - t0)

    def _dispatch_session_group(self, batch: List["rq.CheckRequest"],
                                lane: "_Lane") -> None:
        """Mega-batch session group: append blocks of MANY sessions
        sharing one walk geometry, advanced in waves — wave ``w`` is
        every member session's ``w``-th queued block, and one wave is
        ONE batched kernel launch (``session.advance_group``). Member
        isolation is the group-advance contract: one member's device
        death falls that session to its host monitor while the rest
        of the wave completes on device. Like the solo session path:
        no recovery ladder, no breaker, no device-time attribution."""
        from jepsen_tpu.serve import session as sessmod
        by_sess: Dict[str, List["rq.CheckRequest"]] = {}
        for r in batch:
            by_sess.setdefault(r.session.id, []).append(r)
        sig = f"session-mega/L{len(by_sess)}/A{len(batch)}"
        with self._counts_lock:
            self.dispatch_counts[sig] = \
                self.dispatch_counts.get(sig, 0) + 1
        obs.count("serve.dispatched", len(batch))
        obs.count(f"serve.lane.{lane.idx}.dispatched")
        obs.gauge("serve.inflight", len(batch))
        t0 = time.monotonic()
        waves = max(len(rs) for rs in by_sess.values())
        wave_list = [[rs[w] for rs in by_sess.values() if w < len(rs)]
                     for w in range(waves)]
        stamped: set = set()

        def _stamp(w: int) -> None:
            # per-wave admission bookkeeping (t_dispatch, queue-wait,
            # tenant ledger).  Idempotent so the pipelined path can run
            # it for wave w+1 while wave w walks on device, and the
            # serial fallthrough below still covers every wave.
            if w >= waves or w in stamped:
                return
            stamped.add(w)
            ts = time.monotonic()
            for r in wave_list[w]:
                r.t_dispatch = ts
                obs.histogram(
                    "serve.queue_wait_s",
                    max(0.0, (r.t_coalesce or ts) - r.t_submit))
                self.registry.ledger_record(
                    r.tenant, "dispatched", id=r.id,
                    group=len(batch), ops=int(r.n_ops),
                    session=r.session.id, kind=r.kind,
                    mega=len(wave_list[w]))

        overlap = (dispatch_core.pipeline_k(
            "session-mega", default=dispatch_core.SERVE_PIPE_K) > 1)
        for w, wave in enumerate(wave_list):
            tw = time.monotonic()
            _stamp(w)
            with obs.capture() as cap:
                try:
                    results = sessmod.advance_group(
                        [(r.session, list(r.history), r.seq)
                         for r in wave],
                        should_abort=self._session_abort(tw),
                        overlap_fn=((lambda nw=w + 1: _stamp(nw))
                                    if overlap else None))
                except Exception as e:                  # noqa: BLE001
                    # the group advance's own ladders should have
                    # contained this; a residual crash is recorded,
                    # never fatal, and every member gets a verdict
                    log.warning("mega session wave crashed: %r", e,
                                exc_info=e)
                    obs.engine_fallback("serve-dispatch",
                                        type(e).__name__,
                                        mega=len(wave))
                    results = [{"valid": "unknown",
                                "error": f"{type(e).__name__}: {e}"}
                               for _ in wave]
            now = time.monotonic()
            recs = [rec for rec in cap.ledger
                    if rec.get("event") in ("fallback", "route",
                                            "selected")]
            for r, res in zip(wave, results):
                r.t_collect = now
                # group-level records (no session tag — e.g. the ONE
                # session-mega launch fallback) stitch to every
                # member; session-tagged ones only to their owner
                mine = [rec for rec in recs
                        if rec.get("session") in (None, True,
                                                  r.session.id)]
                r.stitch([{"ts": round(time.time(), 6),
                           "stage": "session-advance",
                           "event": "advance", "session": r.session.id,
                           "seq": r.seq, "mega": len(wave),
                           "wall_s": round(now - tw, 6)}] + mine)
                for rec in mine:
                    if rec.get("event") == "fallback":
                        self.registry.ledger_record(
                            r.tenant, "engine-fallback", id=r.id,
                            stage=rec.get("stage"),
                            cause=rec.get("cause"))
                obs.histogram("serve.session.append_s",
                              now - r.t_submit)
                self._finish(r, res, now - r.t_dispatch, now)
        obs.count("serve.session.advance_wall_s",
                  time.monotonic() - t0)

    def _dispatch(self, batch: List["rq.CheckRequest"],
                  lane: Optional["_Lane"] = None) -> None:
        # single-lane callers (tests drive _dispatch directly) default
        # to lane 0 — the pre-lanes behavior
        if lane is None:
            lane = self._lanes[0]
        # the self-nemesis trigger clock (scheduled clock jumps fire
        # here); never raises for the shipped fault grammar
        faults.fire("tick")
        if batch[0].session is not None:
            if len({r.session.id for r in batch}) > 1:
                # multi-session group: the coalescer only builds one
                # when every block is an append sharing a mega-batch
                # walk-geometry signature
                self._dispatch_session_group(batch, lane)
            else:
                self._dispatch_session(batch, lane)
            return
        req0 = batch[0]
        sig = f"{req0.model_name}/H{len(batch)}"
        with self._counts_lock:
            self.dispatch_counts[sig] = \
                self.dispatch_counts.get(sig, 0) + 1
        obs.count("serve.dispatched", len(batch))
        obs.count(f"serve.lane.{lane.idx}.dispatched")
        obs.gauge("serve.inflight", len(batch))
        t0 = time.monotonic()
        for r in batch:
            # dispatch stamp + queue-wait histogram: admit -> selected
            # into this group (t_coalesce, stamped by next_batch)
            r.t_dispatch = t0
            obs.histogram("serve.queue_wait_s",
                          max(0.0, (r.t_coalesce or t0) - r.t_submit))
            self.registry.ledger_record(
                r.tenant, "dispatched", id=r.id, group=len(batch),
                ops=int(r.packed.n))

        hang = [False]

        def _aborted() -> bool:
            # clean group cancellation: fires only when NO member
            # still wants the verdict (composed into the segmented
            # walk's abort polling by the facade chain) — or when the
            # dispatch itself hangs past its wall-clock cap, in which
            # case survivors are requeued rather than finished
            if self._stop.is_set():
                return True
            if self.dispatch_deadline_s is not None \
                    and time.monotonic() - t0 > self.dispatch_deadline_s:
                if not hang[0]:
                    hang[0] = True
                    obs.engine_fallback("serve-hang",
                                        "DispatchDeadline",
                                        lanes=len(batch),
                                        deadline_s=self
                                        .dispatch_deadline_s)
                return True
            now = time.monotonic()
            return all(r.cancel_requested or r.expired(now)
                       for r in batch)

        # per-request engine options apply to the whole dispatch: the
        # coalescer only groups requests whose options are IDENTICAL
        # (they are part of the compatibility signature), so batch[0]
        # speaks for every member
        kw = dict(self.engine_kw)
        kw.update(req0.opts)
        kw["should_abort"] = _aborted
        n_real = len(batch)
        # pad for ATTRIBUTION (the engine attempts compute their own
        # replication pad per subgroup; this is the full-group value,
        # so device_s + pad_waste_s == dispatch_wall_s by construction)
        pad = self._pad_count(n_real, self._is_txn(req0.model))
        # the dispatcher thread's own obs records (fallbacks, engine
        # selections from the facade chain, retry/bisect/quarantine
        # records from the recovery ladder) are captured here and
        # re-emitted into every member request's stitched trace below
        # — ledgers are thread-isolated, so without this a client-side
        # obs.capture() around submit/poll would never see them
        lane.device_ran = False
        with obs.capture() as cap:
            try:
                results = self._run_recover(batch, kw,
                                            self.retry.max_retries,
                                            lane)
            except Exception as e:                      # noqa: BLE001
                # the ladder itself must be crash-contained too
                log.warning("serve recovery ladder crashed: %r", e,
                            exc_info=e)
                obs.engine_fallback("serve-dispatch",
                                    type(e).__name__,
                                    lanes=len(batch))
                err = {"valid": "unknown",
                       "error": f"{type(e).__name__}: {e}"}
                results = [dict(err) for _ in batch]
        self._publish(batch, results, lane, t0, pad, n_real,
                      cap.ledger, hang, lane.device_ran)

    def _publish(self, batch: List["rq.CheckRequest"], results,
                 lane: "_Lane", t0: float, pad: int, n_real: int,
                 cap_ledger, hang, device_ran: bool) -> None:
        """Results → attribution → stitched traces → finish/requeue:
        the publish tail shared by the blocking dispatch and the
        pipelined collect."""
        if len(results) != len(batch):
            # alignment is the publish contract: a short list would
            # silently strand the tail members un-finished forever
            obs.engine_fallback("serve-dispatch", "ResultMisaligned",
                                lanes=len(batch), got=len(results))
            results = (list(results)
                       + [{"valid": "unknown",
                           "error": "result misaligned"}]
                      * len(batch))[:len(batch)]
        t_collect = time.monotonic()
        elapsed = t_collect - t0
        # the lane attribution clock: with K groups staged on this
        # lane their stage→collect walls overlap, so each group books
        # only the slice of lane wall since the previous collect
        # (collects are FIFO on the lane's own thread, so these
        # intervals partition the lane's busy wall and the device_s +
        # pad_waste_s == dispatch-wall identity stays EXACT under
        # interleaving; serial dispatches have attr_mark <= t0 and
        # book their full elapsed — bit-identical to the pre-pipeline
        # accounting). The overlapped remainder is the pipeline's win,
        # counted pipeline.overlap_s.
        attributed = max(0.0, t_collect - max(t0, lane.attr_mark))
        lane.attr_mark = t_collect
        overlap = elapsed - attributed
        if overlap > 1e-9:
            obs.count("pipeline.overlap_s", overlap)
            obs.count(f"pipeline.lane.{lane.idx}.overlap_s", overlap)

        # device-time attribution: the group's measured kernel wall is
        # amortized over its lanes — each member (one real lane) gets
        # wall/lanes, the replicated pad lanes' share is padding waste
        # (a first-class counter). share*n_real + waste == wall, so
        # attributed device-seconds reconcile with dispatch wall by
        # construction (asserted within 2% in tests). The per-lane
        # copies make the same identity hold for each dispatch lane
        # alone: sum_k lane.k.device_s + lane.k.pad_waste_s covers
        # every device second the daemon spent, attributed to the
        # lane that spent it.
        lanes = n_real + pad
        if device_ran:
            share = attributed / lanes
            waste = share * pad
            obs.histogram("serve.dispatch_wall_s", attributed)
            obs.count("serve.device_s", share * n_real)
            if self._n_ranks > 1:
                obs.count("dist.device_s", share * n_real)
            obs.count("serve.pad_waste_s", waste)
            obs.count(f"serve.lane.{lane.idx}.device_s",
                      share * n_real)
            obs.count(f"serve.lane.{lane.idx}.pad_waste_s", waste)
        else:
            # breaker-open dispatch served entirely host-side: no
            # kernel wall, no pad lanes — booking it as device time
            # would corrupt the attribution operators read during a
            # degraded period (its wall gets its own counter)
            share = waste = 0.0
            obs.count("serve.breaker.host_wall_s", elapsed)

        # stitched per-request trace: the group-level dispatch record
        # plus every ledger record the dispatch produced, re-emitted
        # per member with the request id. Fallbacks/swallows also land
        # in the member's TENANT serve ledger, so "no silent fallback"
        # stays assertable from the client side (GET /check/<id> and
        # GET /stats), not just from inside the daemon process.
        engine_recs = [r for r in cap_ledger
                       if r.get("event") in ("selected", "fallback",
                                             "swallowed", "route",
                                             "skipped")]
        disp_rec = {"ts": round(time.time(), 6),
                    "stage": "serve-dispatch", "event": "dispatch",
                    "group": lanes, "real": n_real, "pad": pad,
                    "wall_s": round(elapsed, 6),
                    "device_s": round(share, 9),
                    "pad_waste_s": round(waste, 9)}
        now = time.monotonic()
        for req, res in zip(batch, results):
            req.t_collect = t_collect
            req.device_s = share
            req.stitch([disp_rec] + engine_recs)
            self.registry.add_device_time(req.tenant, share)
            for r in engine_recs:
                if r.get("event") in ("fallback", "swallowed"):
                    self.registry.ledger_record(
                        req.tenant, f"engine-{r['event']}",
                        id=req.id, stage=r.get("stage"),
                        cause=r.get("cause"))
            if (hang[0] and res.get("valid") not in (True, False)
                    and not res.get("quarantined")
                    and not req.cancel_requested
                    and not req.expired(now)
                    and req.requeues < self.retry.max_requeues):
                # a hung dispatch was aborted past its wall-clock cap:
                # this survivor still wants its verdict — requeue it
                # (bounded by the shared retry policy) instead of
                # publishing the abort's "unknown"
                self._requeue(req)
            else:
                self._finish(req, res, elapsed, now)

    # -- the pipelined stage/collect pair --------------------------------
    def _stage(self, batch: List["rq.CheckRequest"],
               lane: "_Lane") -> Optional["_StagedDispatch"]:
        """STAGE one one-shot group: probe the staged engine route
        (:func:`facade.stage_check_many_packed` — host pack + device
        puts + kernel launches, nothing fetched) and, if it admits,
        commit the per-request dispatch bookkeeping and return the
        in-flight handle. Returns None — with NO request-visible side
        effects — when the group is not stageable (sessions, txn,
        singletons, breaker-open lane, per-request opts that force
        another route, engine gates closed), so the caller's blocking
        path runs exactly as before the pipeline existed."""
        from jepsen_tpu.checkers import facade
        req0 = batch[0]
        if req0.session is not None or len(batch) < 2:
            return None
        if self._is_txn(req0.model):
            return None
        if lane.breaker.route() == "host":
            return None
        if faults.enabled():
            # armed fault injection exercises the blocking path's fire
            # points (tick/dispatch/device) — staging would skip them
            return None
        kw = dict(self.engine_kw)
        kw.update(req0.opts)
        if kw.get("force_host"):
            return None
        t0 = time.monotonic()
        hang = [False]

        def _aborted() -> bool:
            if self._stop.is_set():
                return True
            if self.dispatch_deadline_s is not None \
                    and time.monotonic() - t0 > self.dispatch_deadline_s:
                if not hang[0]:
                    hang[0] = True
                    obs.engine_fallback("serve-hang",
                                        "DispatchDeadline",
                                        lanes=len(batch),
                                        deadline_s=self
                                        .dispatch_deadline_s)
                return True
            now = time.monotonic()
            return all(r.cancel_requested or r.expired(now)
                       for r in batch)

        kw["should_abort"] = _aborted
        n_real = len(batch)
        pad = self._pad_count(n_real, False)
        packed_list, _pad = self._padded_list(batch)
        with obs.capture() as cap:
            with obs.span("pipeline.stage", model=req0.model_name,
                          lanes=len(batch), reqs=[r.id for r in batch]):
                handle = facade.stage_check_many_packed(
                    req0.model, packed_list, kw)
        if handle is None:
            return None
        # committed: the group IS dispatched — same bookkeeping as the
        # blocking path's pre-engine half
        faults.fire("tick")
        sig = f"{req0.model_name}/H{len(batch)}"
        with self._counts_lock:
            self.dispatch_counts[sig] = \
                self.dispatch_counts.get(sig, 0) + 1
        obs.count("serve.dispatched", len(batch))
        obs.count(f"serve.lane.{lane.idx}.dispatched")
        obs.gauge("serve.inflight", len(batch))
        if pad:
            obs.count("serve.pad_lanes", pad)
        for r in batch:
            r.t_dispatch = t0
            obs.histogram("serve.queue_wait_s",
                          max(0.0, (r.t_coalesce or t0) - r.t_submit))
            self.registry.ledger_record(
                r.tenant, "dispatched", id=r.id, group=len(batch),
                ops=int(r.packed.n))
        return _StagedDispatch(batch, lane.idx, kw, hang, t0, pad,
                               n_real, handle, list(cap.ledger))

    def _collect_staged(self, staged: "_StagedDispatch",
                        lane: "_Lane") -> None:
        """COLLECT one staged group: fetch its verdict words, publish
        through the shared tail. A collect-side failure (jax surfaces
        walk errors at first fetch) feeds the lane breaker and drops
        into the UNCHANGED recovery ladder — retry → bisect → host
        rescue → quarantine — on the retained requests, so a staged
        group that dies gets exactly the pre-pipeline treatment."""
        batch = staged.batch
        req0 = batch[0]
        lane.device_ran = True      # the stage launched device work
        with obs.capture() as cap:
            try:
                with obs.span("serve.dispatch",
                              model=req0.model_name,
                              lanes=len(batch),
                              reqs=[r.id for r in batch]):
                    results = staged.handle.collect()[:len(batch)]
                lane.breaker.record_success()
            except Exception as e:                      # noqa: BLE001
                # jtlint: ok fallback — collect death enters the
                # ordinary recovery ladder below; the fallback record
                # mirrors the blocking path's per-attempt record
                log.warning("staged collect failed (lanes=%d): %r",
                            len(batch), e, exc_info=e)
                obs.engine_fallback("serve-dispatch",
                                    type(e).__name__,
                                    lanes=len(batch), staged=True)
                lane.breaker.record_failure()
                try:
                    results = self._run_recover(
                        batch, staged.kw, self.retry.max_retries, lane)
                except Exception as e2:                 # noqa: BLE001
                    # the ladder itself must be crash-contained too
                    log.warning("serve recovery ladder crashed: %r",
                                e2, exc_info=e2)
                    obs.engine_fallback("serve-dispatch",
                                        type(e2).__name__,
                                        lanes=len(batch))
                    err = {"valid": "unknown",
                           "error": f"{type(e2).__name__}: {e2}"}
                    results = [dict(err) for _ in batch]
        self._publish(batch, results, lane, staged.t0, staged.pad,
                      staged.n_real, staged.cap_recs + cap.ledger,
                      staged.hang, True)

    # -- completion ------------------------------------------------------
    def _requeue(self, req: "rq.CheckRequest") -> None:
        req.requeues += 1
        req.status = rq.QUEUED
        req.t_coalesce = req.t_dispatch = req.t_collect = None
        obs.count("serve.retry.requeued")
        obs.decision("serve-retry", "requeued", id=req.id,
                     requeues=req.requeues)
        self.registry.ledger_record(req.tenant, "requeued", id=req.id,
                                    requeues=req.requeues)
        self.queue.submit(req, force=True)

    def _finish(self, req: "rq.CheckRequest", res: Dict[str, Any],
                elapsed: float, now: float) -> None:
        if req.cancel_requested:
            status = rq.CANCELLED
            obs.count("serve.cancelled")
        elif res.get("quarantined"):
            # the bisect ladder cornered this member as the poison:
            # structured terminal state, never a silent "unknown"
            # (counters/ledger records bumped where the quarantine
            # decision was made, in _run_recover)
            status = rq.QUARANTINED
        elif req.expired(now) and res.get("valid") not in (True, False):
            # the walk was aborted (or still unknown) past the
            # deadline: a timeout, not a verdict
            status = rq.TIMEOUT
            res = {"valid": "unknown", "cause": "deadline",
                   **{k: v for k, v in res.items() if k != "valid"}}
            obs.count("serve.timeout")
            obs.engine_fallback("serve-timeout", "DeadlineExpired",
                                tenant=req.tenant, ops=req.n_ops,
                                dispatched=True)
        else:
            # a conclusive verdict that merely finished late is still
            # the verdict — deadline enforcement is about not burning
            # device time, not about discarding finished work
            status = rq.DONE
            obs.count("serve.completed")
            # latency histograms observed exactly where serve.completed
            # bumps, so the CI invariant "e2e histogram count equals
            # completed requests" holds at every /metrics scrape
            obs.histogram("serve.e2e_s", now - req.t_submit)
            obs.histogram("serve.service_s",
                          now - (req.t_coalesce or req.t_dispatch
                                 or req.t_submit))
        if self.persist and status == rq.DONE \
                and req.session is None:
            # session blocks are not persisted as store runs: their
            # durable record is the session journal (replayable), and
            # a browsable run per append would bury real runs
            try:
                # provisional done stamp so the PERSISTED waterfall
                # carries its publish stage (registry.finish re-stamps
                # a hair later; the live GET view uses that one)
                req.t_done = now
                req.run_dir = self._persist(req, res)
            except Exception as e:                      # noqa: BLE001
                # never silent: the verdict still publishes, but the
                # missing run dir is a recorded degradation
                log.warning("serve persist failed for %s: %s",
                            req.id, e)
                obs.engine_fallback("serve-persist",
                                    type(e).__name__, id=req.id)
        self.registry.finish(req, status, res)
        self.registry.ledger_record(
            req.tenant, status, id=req.id,
            valid=res.get("valid"), engine=res.get("engine"),
            dispatch_s=round(elapsed, 6),
            device_s=round(req.device_s or 0.0, 9),
            latency_s=round(now - req.t_submit, 6))
        obs.count(
            f"serve.tenant.{self.registry.bucket_tenant(req.tenant)}"
            f".{status}")

    def _finish_timeout_queued(self, req: "rq.CheckRequest") -> None:
        """Queue-side deadline expiry (never dispatched)."""
        self.registry.finish(req, rq.TIMEOUT,
                             {"valid": "unknown", "cause": "deadline",
                              "queued-only": True})
        self.registry.ledger_record(req.tenant, rq.TIMEOUT, id=req.id,
                                    queued_only=True)
        obs.count(
            f"serve.tenant.{self.registry.bucket_tenant(req.tenant)}"
            f".timeout")

    # -- persistence -----------------------------------------------------
    def _persist(self, req: "rq.CheckRequest",
                 res: Dict[str, Any]) -> str:
        """Write the request as a browsable store run
        (:func:`jepsen_tpu.store.save_check` —
        ``<root>/serve-<model>/<ts>-<id>/``) so the existing
        ``web.py`` results browser renders daemon traffic exactly
        like CLI runs."""
        from jepsen_tpu import store
        faults.fire("persist", tenants=[req.tenant])
        assert self.store_root is not None
        out = dict(res)
        out["serve"] = {"id": req.id, "tenant": req.tenant,
                        "latency-s": round(
                            time.monotonic() - req.t_submit, 6),
                        "device-s": round(req.device_s or 0.0, 9),
                        "waterfall": req.waterfall(),
                        "trace": [dict(r) for r in req.trace]}
        return store.save_check(self.store_root,
                                f"serve-{req.model_name}", req.id,
                                list(req.history), out)

    # -- stats -----------------------------------------------------------
    def stats(self, snap: Optional[Dict[str, Any]] = None
              ) -> Dict[str, Any]:
        if snap is None:
            snap = obs.core.GLOBAL.snapshot()
        counters = {k: v for k, v in snap["counters"].items()
                    if k.startswith(("serve.", "engine.", "lockstep.",
                                     "compile_cache.", "memo_cache.",
                                     "transfer.", "pipeline."))}
        with self._counts_lock:
            dispatch = dict(self.dispatch_counts)
        out = {
            "queue": {"depth": self.queue.depth(),
                      "max_depth": self.queue.max_depth,
                      "inflight": self.queue.inflight(),
                      "group": self.queue.group,
                      "max_inflight_per_tenant":
                          self.queue.max_inflight_per_tenant},
            "dispatch": dispatch,
            "counters": counters,
            # headline digests of the serve-path latency histograms
            # (full bucket ladders live on GET /metrics)
            "histograms": {k: obs.hist_summary(h)
                           for k, h in snap["histograms"].items()
                           if k.startswith("serve.")},
            "timeseries": self.ring.points(),
            "profile": self.profile_state(),
            # degradation surface: breaker state + retry policy, so
            # /stats, stats.json, and the /engine dashboard all see
            # the same health the chaos harness asserts on. With
            # multiple lanes, "breaker" stays lane 0's (back-compat)
            # and the per-lane view + any-lane-degraded aggregate
            # live under "lanes".
            "breaker": self.breaker.to_json(),
            "degraded": any(ln.breaker.degraded
                            for ln in self._lanes),
            "retry": self.retry.to_json(),
            "lanes": {
                "n": self.lanes_n,
                "loads": self.queue.lane_loads(),
                "breakers": [ln.breaker.to_json()
                             for ln in self._lanes],
            },
        }
        if self.journal is not None:
            out["journal"] = self.journal.stats()
        if self.sessions is not None:
            # open-session census: count, oldest age, per-tenant —
            # the /engine dashboard's "open sessions" row
            out["sessions"] = self.sessions.census()
        out.update(self.registry.stats())
        return out

    def _write_stats_file(self, snap: Optional[Dict[str, Any]] = None
                          ) -> None:
        """Drop the latest stats snapshot under the store root
        (best-effort) so the results browser's ``/engine`` page can
        render a daemon it does not share a process with."""
        if not self.store_root:
            return
        try:
            d = os.path.join(self.store_root, "serve")
            os.makedirs(d, exist_ok=True)
            # per-thread tmp name: N lane threads write stats
            # concurrently, and two writers sharing one tmp path
            # would interleave open("w")/replace and tear stats.json
            tmp = os.path.join(
                d, f".stats.json.{threading.get_ident()}.tmp")
            with open(tmp, "w") as f:
                json.dump({"ts": time.time(), **self.stats(snap)}, f,
                          default=str)
            os.replace(tmp, os.path.join(d, "stats.json"))
        # jtlint: ok fallback — per-dispatch stats are advisory, never fatal
        except Exception:                               # noqa: BLE001
            pass                # stats are advisory, never fatal
