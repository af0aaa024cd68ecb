"""Online (live) linearizability monitoring — no upstream analogue.

Upstream Jepsen is strictly post-hoc: the history is analyzed after the
run ends (``jepsen.core/run!`` → ``checker/check-safe``, SURVEY.md §3.1),
so a test that violated linearizability in its first second still runs to
completion before anyone finds out. This monitor verifies the history
WHILE it streams, failing fast the moment a violation appears.

Two flush strategies:

- ``mode="incremental"`` (default): the monitor carries the dense
  reachability config set ``R[S, M]`` (exactly the state of
  :mod:`jepsen_tpu.checkers.reach`'s walk) across flushes and advances
  it only through NEW return events, making total monitoring work O(n)
  over the whole run instead of the O(n²) of re-checking every prefix.
  The carried advance is restricted to the *settled* prefix — return
  events whose entire pending map is resolved (completed with a known
  value, failed, or crashed) — because an op's transition is not known
  until its value is (a concurrent read may linearize before its return,
  but only with the value it eventually returns). The unsettled tail is
  usually the in-flight window (≤ concurrency ops) — though one
  long-pending op queues every later return behind it — and a bounded
  prefix of it is checked each flush from a copy of the carried set
  with unresolved ops treated as crashed: an over-approximation, so a
  tail alarm is still sound. On
  anything the dense representation cannot hold (slot overflow, state
  explosion, model without a finite memo) the monitor permanently falls
  back to the re-check strategy below. Each flush's settled batch is
  walked by the bit-packed C++ engine (``native/preproc.cpp
  jt_walk_dense``, ~1 µs/return). Measured: a 100k-op cas stream
  monitors end-to-end in ~1.2 s of host time (~86k ops/s sustained at
  a 256-event flush cadence, each return walked exactly once; round 2's
  per-return NumPy walk took ~8.8 s), where prefix re-checking at a
  128-op cadence does ~39M op-re-checks plus a device round-trip per
  flush.
- ``mode="recheck"``: re-check the entire recorded prefix on each
  cadence tick with the production engines. Simple and exact, but total
  work grows quadratically with history length.

Soundness (both modes):

- *No false alarms.* Still-running invocations enter the analysis as
  crashed ops (they may linearize at any point or never — both
  explored), and unresolved read values are ``None`` wildcards. Both
  over-approximate the constraints the finished history will impose, so
  a prefix reported invalid is genuinely invalid.
- *Fail-fast is permanent.* Linearizability is prefix-closed: an
  invalid prefix can never be repaired by more ops, so the monitor
  stops after the first violation and the runner may abort the test.
- *Eventually exact.* At :meth:`OnlineLinearizable.stop` every op has
  resolved (run over: still-pending means crashed), so the incremental
  monitor's final verdict is the exact full-history verdict; in
  recheck mode the final post-hoc check remains the source of truth
  for any inconclusive tail.
"""
from __future__ import annotations

import heapq
import logging
import threading
import time as _time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from jepsen_tpu.models import Model
from jepsen_tpu.op import FAIL, INFO, INVOKE, OK, Op
from jepsen_tpu.util import hashable

log = logging.getLogger("jepsen.online")


class _Binding:
    """One invocation's lifetime: its slot, invoke op (for reporting),
    and resolution status. The op's transition id is internable only
    once its value is known (reads carry the value on the completion)."""

    __slots__ = ("slot", "inv", "status", "value", "oid")

    def __init__(self, slot: int, inv: Op):
        self.slot = slot
        self.inv = inv
        self.status = "pending"         # pending | ok | fail | crashed
        self.value = inv.value          # Entry rule: completion value wins
        self.oid = -1                   # interned op id once resolved
                                        # (alphabet ids are append-only)

    def resolve(self, kind: str, value: Any) -> None:
        self.status = kind
        if kind == "ok" and value is not None:
            self.value = value

    @property
    def resolved(self) -> bool:
        return self.status != "pending"


class _Overflow(Exception):
    """The dense representation cannot hold this run — permanent fallback
    to recheck mode."""


def _walk_return(R: np.ndarray, rows: np.ndarray, jr: int,
                 P: np.ndarray) -> np.ndarray:
    """One return event on the dense config set, NumPy edition of
    :mod:`jepsen_tpu.checkers.reach`'s fire-to-fixpoint + projection:
    ``R`` bool[S, M]; ``rows[j]`` the pending op in slot j (or -1);
    ``jr`` the returning slot; ``P`` bool[O, S, S]."""
    M = R.shape[1]
    m = np.arange(M)
    while True:
        new = R.copy()
        for j, o in enumerate(rows):
            if o < 0:
                continue
            bit = 1 << j
            clear = np.nonzero((m & bit) == 0)[0]
            img = P[o].T @ R[:, clear]          # fired images of bit-clear
            new[:, clear | bit] |= img
        if (new == R).all():
            break
        R = new
    bit = 1 << jr
    kept = np.nonzero((m & bit) != 0)[0]
    out = np.zeros_like(R)
    out[:, kept ^ bit] = R[:, kept]
    return out


class IncrementalEngine:
    """O(n) streaming linearizability state: the dense config set carried
    across flushes, advanced through settled return events only (module
    docstring). A flush's settleable returns are walked in ONE call to
    the bit-packed C++ walk (:meth:`_walk_batch_native`,
    ``native/preproc.cpp jt_walk_dense`` — ~1 µs/return with zero
    dispatch cost; the accelerator is never involved: the [S, M] set is
    a few machine words and one device round-trip was measured, on an
    earlier remote device, to cost more than a whole flush; unmeasured
    on the chip). Without the native lib the per-return NumPy fixpoint
    (:func:`_walk_return`) remains, and doubles as the differential
    reference in ``tests/test_online.py``."""

    def __init__(self, model: Model, *, max_states: int = 100_000,
                 max_slots: int = 20, max_dense: int = 1 << 22):
        self.model = model
        self.max_states = max_states
        self.max_slots = max_slots
        self.max_dense = max_dense
        self.alphabet: Dict[Tuple[Any, Any], int] = {}
        self.alpha_ops: List[Op] = []
        self.memo = None
        self.P: Optional[np.ndarray] = None      # bool [O, S, S]
        self.W = 1
        self.R: Optional[np.ndarray] = None      # bool [S, 2^W]
        self._free: List[int] = []
        self._hi = 0
        self._proc: Dict[Any, _Binding] = {}     # live invocations
        self._crashed: List[_Binding] = []       # forever-pending
        # FIFO of return events awaiting settlement, in real-time order:
        # (returning binding, pending-map snapshot of binding refs)
        self._queue: deque = deque()
        self.settled_returns = 0
        self.walked_events = 0                   # O(n) telemetry for tests
        self.violation: Optional[Dict[str, Any]] = None

    # -- alphabet / memo ------------------------------------------------------

    def _intern_batch(self, keys) -> None:
        """Add every unseen ``(f, value)`` to the alphabet with ONE memo
        rebuild + state re-encode for the whole batch (a flush that
        surfaces k new pairs must not pay k O(S²·O) rebuilds).
        Transient wildcard entries from the tail alarm (an unresolved
        read's ``(f, None)``) are bounded — one per function name, the
        same entry a genuinely crashed read would intern."""
        fresh = []
        seen = set()
        for f, v in keys:
            k = (f, hashable(v))
            if k not in self.alphabet and k not in seen:
                seen.add(k)
                fresh.append((k, f, v))
        if not fresh:
            return
        from jepsen_tpu.models.memo import StateExplosion, memo_ops
        from jepsen_tpu.op import invoke as mk_invoke
        for k, f, v in fresh:
            self.alphabet[k] = len(self.alpha_ops)
            self.alpha_ops.append(mk_invoke(0, f, v))
        old_memo, old_R = self.memo, self.R
        try:
            self.memo = memo_ops(self.model, tuple(self.alpha_ops),
                                 max_states=self.max_states)
        except StateExplosion as e:
            raise _Overflow(str(e)) from e
        S = self.memo.n_states
        if S * (1 << self.W) > self.max_dense:
            raise _Overflow(f"dense config space {S}x{1 << self.W}")
        T = self.memo.table
        P = np.zeros((len(self.alpha_ops), S, S), bool)
        s = np.arange(S)
        for o in range(T.shape[1]):
            okc = T[:, o] >= 0
            P[o, s[okc], T[okc, o]] = True
        self.P = P
        R = np.zeros((S, 1 << self.W), bool)
        if old_R is None:
            R[0, 0] = True
        else:
            # re-encode carried states: the wider-alphabet BFS reaches
            # a superset of the old states
            new_id = {st: i for i, st in enumerate(self.memo.states)}
            for sid in np.nonzero(old_R.any(axis=1))[0]:
                R[new_id[old_memo.states[sid]]] |= old_R[sid]
        self.R = R

    def _intern_rows(self, b: _Binding, snap: List[_Binding],
                     n_crashed: int) -> np.ndarray:
        """Materialize a return event's pending map to op-id rows —
        called only once every binding in it is resolved (or, for the
        tail alarm, with unresolved ops as crashed wildcards).
        ``n_crashed`` is the crashed-list length at the return's feed
        time (crashes recorded later were invoked later and are NOT in
        this event's pending map). Interning happens BEFORE any caller
        copies ``self.R``: it may rebuild the state coding."""
        members = snap + self._crashed[:n_crashed] + [b]
        self._intern_batch([(x.inv.f, x.value)
                            for x in members
                            if x.status != "fail" and x.oid < 0])
        rows = np.full(self.W, -1, np.int64)
        for x in members:
            if x.status == "fail":
                continue            # stripped, exactly like post-hoc
            if x.oid >= 0:
                rows[x.slot] = x.oid
                continue
            oid = self.alphabet[(x.inv.f, hashable(x.value))]
            if x.resolved:
                # ids are append-only, so a resolved binding's id is
                # final; unresolved tail-alarm wildcards stay uncached
                # (their value may change at resolution)
                x.oid = oid
            rows[x.slot] = oid
        return rows

    def _grow_slots(self, slot: int) -> None:
        if slot < self.W:
            return
        if slot >= self.max_slots:
            raise _Overflow(f"history needs > {self.max_slots} slots")
        W2 = slot + 1
        S = self.R.shape[0] if self.R is not None else 2
        if S * (1 << W2) > self.max_dense:
            raise _Overflow(f"dense config space {S}x{1 << W2}")
        if self.R is not None:
            # zero-embed: new slots are free, their bits 0 in every config
            R2 = np.zeros((self.R.shape[0], 1 << W2), bool)
            R2[:, :self.R.shape[1]] = self.R
            self.R = R2
        self.W = W2

    # -- ingestion ------------------------------------------------------------

    def feed(self, op: Op) -> None:
        if op.process == "nemesis":
            return
        if op.type == INVOKE:
            if op.process in self._proc:
                raise _Overflow(f"double invoke by {op.process}")
            slot = heapq.heappop(self._free) if self._free else self._hi
            if slot == self._hi:
                self._hi += 1
            self._grow_slots(slot)
            self._proc[op.process] = _Binding(slot, op)
            return
        b = self._proc.pop(op.process, None)
        if b is None:
            return                      # completion without invoke: ignore
        if op.type == OK:
            b.resolve("ok", op.value)
            # pending at this return: live invocations + the
            # forever-crashed ops so far. The crashed list only appends,
            # so its membership at THIS moment is captured by its length
            # alone — an O(1) snapshot instead of copying an ever-growing
            # list per return. The slot frees NOW (walk order still
            # projects it correctly: a reused slot's new op cannot fire
            # before this return's event is walked, so its bit is still
            # clear then)
            self._queue.append((b, list(self._proc.values()),
                                len(self._crashed)))
            heapq.heappush(self._free, b.slot)
        elif op.type == FAIL:
            # definitely no effect: stripped. The carried set holds no
            # trace of it — settlement requires every snapshot binding
            # resolved, so no return event that saw this op pending has
            # been walked yet; those still queued skip it at settlement
            # (exactly the post-hoc strip)
            b.resolve("fail", None)
            heapq.heappush(self._free, b.slot)
        elif op.type == INFO:
            # crashed: resolved (fires anytime or never), holds its slot
            # forever like the post-hoc walk's forever-pending entries
            b.resolve("crashed", op.value)
            self._crashed.append(b)

    # -- the walk -------------------------------------------------------------

    def _intern_items(self, items) -> List[np.ndarray]:
        """Intern every member of every queued item in ONE batch (the
        memo may rebuild once, not per return), then materialize each
        item's pending-op rows."""
        keys = []
        for b, snap, n_crashed in items:
            keys.extend((x.inv.f, x.value)
                        for x in snap + self._crashed[:n_crashed] + [b]
                        if x.status != "fail" and x.oid < 0)
        self._intern_batch(keys)
        return [self._intern_rows(b, snap, n_crashed)
                for b, snap, n_crashed in items]

    def _walk_batch_native(self, R0: np.ndarray, rows_list, slots
                           ) -> Optional[Tuple[np.ndarray, int]]:
        """Walk a batch of return events through the bit-packed C++
        walk (``preproc_native.walk_dense``): the [S, M] set packs to
        S·M/64 machine words, so word-parallel C++ does ~1 µs/return
        with zero dispatch or compile cost (the per-return NumPy
        fixpoint is ~170 µs/return, and an XLA CPU walk pays ~ms of
        dispatch per flush plus a compile per geometry). Returns
        ``(R_final, dead_idx)`` (``dead_idx = -1`` when the set
        survived — the exact index comes straight from the walk), or
        None when the native lib is unavailable."""
        from jepsen_tpu.checkers import preproc_native

        if not preproc_native.available():
            return None
        L = len(rows_list)
        W, M = self.W, 1 << self.W
        R_words = _pack_words(R0, M)
        rows_arr = np.asarray(rows_list, np.int32).reshape(L, W)
        dead = preproc_native.walk_dense(
            self.memo.table, R_words, W,
            np.asarray(slots, np.int32), rows_arr)
        if dead is None:
            return None
        return _unpack_words(R_words, M), int(dead)

    def advance(self, run_over: bool = False) -> Optional[Dict[str, Any]]:
        """Walk the settled prefix of queued returns; with ``run_over``
        every still-pending op resolves as crashed first (the run is
        over — the verdict becomes the exact full-history one). Returns
        the violation, if one is found."""
        if self.violation is not None:
            return self.violation
        if run_over:
            for p, b in list(self._proc.items()):
                b.resolve("crashed", b.inv.value)
                del self._proc[p]
                self._crashed.append(b)
        # collect every currently-settleable return, then walk them in
        # one XLA call (per-return NumPy below the dispatch break-even)
        items = []
        while self._queue:
            b, snap, n_crashed = self._queue[0]
            if not all(x.resolved for x in snap):
                break
            self._queue.popleft()
            items.append((b, snap, n_crashed))
        if not items:
            return None
        rows_list = self._intern_items(items)
        slots = np.fromiter((b.slot for b, _, _ in items), np.int32,
                            count=len(items))
        walked = self._walk_batch_native(self.R, rows_list, slots)
        if walked is None:              # no native lib: NumPy walk
            for i, (b, _, _) in enumerate(items):
                self.R = _walk_return(self.R, rows_list[i], b.slot,
                                      self.P)
                self.settled_returns += 1
                self.walked_events += 1
                if not self.R.any():
                    self.violation = self._violation_at(b, self.R)
                    return self.violation
            return None
        R_final, dead = walked
        if dead < 0:
            self.R = R_final
            self.settled_returns += len(items)
            self.walked_events += len(items)
            return None
        self.R = R_final
        self.settled_returns += dead + 1
        self.walked_events += dead + 1
        # items[dead+1:] were dequeued but never walked; they are NOT
        # re-queued because a violation is terminal for this engine
        # (every later advance() short-circuits on self.violation, and
        # there is deliberately no reset/continue path — a monitor that
        # has proven non-linearizability has nothing more to decide)
        self.violation = self._violation_at(items[dead][0], R_final)
        return self.violation

    # per-flush cap on the tail walk: the queue can grow far beyond the
    # in-flight window when ONE op stays pending for a long time (every
    # later return blocks behind it), and re-walking the whole queue
    # each flush would be the O(n²) this engine exists to avoid. The
    # oldest _TAIL_CAP events still give a sound early alarm; deeper
    # events wait for settlement (or the exact final flush).
    _TAIL_CAP = 512

    def tail_alarm(self) -> Optional[Dict[str, Any]]:
        """Check (a bounded prefix of) the unsettled tail from a copy of
        the carried set with unresolved ops treated as crashed (they may
        fire anytime or never — a sound over-approximation of any
        eventual completion, so an alarm here is a real violation).
        Early detection only; the carried state is untouched."""
        if self.violation is not None or not self._queue:
            return None
        items = list(self._queue)[:self._TAIL_CAP]
        # intern everything FIRST: interning may re-encode self.R
        rows_list = self._intern_items(items)
        slots = np.fromiter((b.slot for b, _, _ in items), np.int32,
                            count=len(items))
        walked = self._walk_batch_native(self.R, rows_list, slots)
        if walked is None:              # no native lib: NumPy walk
            R = self.R.copy()
            for i, (b, _, _) in enumerate(items):
                R = _walk_return(R, rows_list[i], b.slot, self.P)
                if not R.any():
                    self.violation = self._violation_at(b, R)
                    return self.violation
            return None
        R_final, dead = walked
        if dead >= 0:
            self.violation = self._violation_at(items[dead][0], R_final)
            return self.violation
        return None

    def _violation_at(self, b: _Binding, R) -> Dict[str, Any]:
        op = b.inv.with_(type=OK, value=b.value)
        return {"valid": False, "engine": "online-incremental",
                "op": op.to_dict(),
                "settled-returns": self.settled_returns}

    def in_flight(self) -> int:
        """Returns not yet conclusively walked + live invocations (the
        monitor's unsettled window)."""
        return len(self._queue) + len(self._proc)


def _pack_words(R: np.ndarray, M: int) -> np.ndarray:
    """Bit-pack the mask axis of a bool [S, M] set into u64 words."""
    packed8 = np.packbits(R, axis=1, bitorder="little")
    n_words = max(1, -(-M // 64))
    buf = np.zeros((R.shape[0], n_words * 8), np.uint8)
    buf[:, :packed8.shape[1]] = packed8
    return np.ascontiguousarray(buf).view(np.uint64)


def _unpack_words(words: np.ndarray, M: int) -> np.ndarray:
    return np.unpackbits(words.view(np.uint8), axis=1,
                         bitorder="little")[:, :M].astype(bool)


_TCODE = {INVOKE: 0, OK: 1, FAIL: 2, INFO: 3}
_SCALAR_T = (int, str, bool, float)


class NativeStreamEngine:
    """The incremental monitor with its per-op bookkeeping in C++
    (``native/preproc.cpp jt_mon_*`` via
    :class:`~jepsen_tpu.checkers.preproc_native.Monitor`): profiling
    the Python :class:`IncrementalEngine` on a 100k-op stream showed
    ~95% of its ~1.9 s was host object churn — per-return snapshot
    lists, per-member interning (428k ``hashable`` calls), per-op dict
    traffic — and only ~0.1 s the actual bit-packed walk. Here
    ``feed`` just buffers; ``advance`` drains the buffer into three
    int arrays, makes ONE native feed call (slot assignment, settle
    queue, snapshots) and ONE native advance call (settled-returns
    walk), leaving Python only value interning (model-dependent) and
    the carried set ``R`` (re-encoded on the rare memo/W growth).
    Same soundness story and same verdicts as IncrementalEngine
    (differentially tested in ``tests/test_online.py`` and the
    cross-engine fuzzer); measured ~6-8x faster end-to-end. The
    accelerator is deliberately NOT involved: per-flush XLA dispatch
    lost on every axis measured in round 3, on an earlier remote
    device (unmeasured on the chip)."""

    _TAIL_CAP = 512

    def __init__(self, model: Model, *, max_states: int = 100_000,
                 max_slots: int = 20, max_dense: int = 1 << 22):
        from jepsen_tpu.checkers import preproc_native
        self.model = model
        self.max_states = max_states
        self.max_slots = max_slots
        self.max_dense = max_dense
        self._mon = preproc_native.Monitor(max_slots)
        self.alphabet: Dict[Tuple[Any, Any], int] = {}
        self.alpha_ops: List[Op] = []
        self.memo = None
        self.W = 1
        self.R: Optional[np.ndarray] = None      # bool [S, 2^W]
        self._buf: List[Op] = []
        self._live_inv: Dict[Any, Tuple[int, Op]] = {}
        self._bind_ops: List[Op] = []            # bind id -> invoke op
        self._bind_val: Dict[int, Any] = {}      # bind id -> final value
        self._procmap: Dict[Any, int] = {}       # non-int process ids
        self._memo_dirty = False
        self.settled_returns = 0
        self.walked_events = 0
        self.violation: Optional[Dict[str, Any]] = None

    # -- interning ------------------------------------------------------------

    def _pkey(self, p) -> int:
        # disjoint encodings: genuine int processes land on evens,
        # interned non-int processes on odds — a history mixing
        # process "a" with process -1 can never collide in the native
        # live map
        if isinstance(p, int):
            return p * 2
        v = self._procmap.get(p)
        if v is None:
            v = len(self._procmap) * 2 + 1
            self._procmap[p] = v
        return v

    def _oid(self, f: str, v: Any) -> int:
        # fast path: scalar values (and tuples of scalars — cas pairs)
        # ARE their hashable form, skipping the recursive converter
        # that dominated the Python engine
        tv = type(v)
        if v is None or tv in _SCALAR_T:
            k = (f, v)
        elif tv is tuple and all(
                x is None or type(x) in _SCALAR_T for x in v):
            k = (f, v)
        else:
            k = (f, hashable(v))
        o = self.alphabet.get(k)
        if o is None:
            from jepsen_tpu.op import invoke as mk_invoke
            o = len(self.alpha_ops)
            self.alphabet[k] = o
            self.alpha_ops.append(mk_invoke(0, f, v))
            self._memo_dirty = True
        return o

    # -- memo / geometry growth ----------------------------------------------

    def _rebuild_memo(self) -> None:
        from jepsen_tpu.models.memo import StateExplosion, memo_ops
        old_memo, old_R = self.memo, self.R
        try:
            self.memo = memo_ops(self.model, tuple(self.alpha_ops),
                                 max_states=self.max_states)
        except StateExplosion as e:
            raise _Overflow(str(e)) from e
        S = self.memo.n_states
        if S * (1 << self.W) > self.max_dense:
            raise _Overflow(f"dense config space {S}x{1 << self.W}")
        R = np.zeros((S, 1 << self.W), bool)
        if old_R is None:
            R[0, 0] = True
        else:
            new_id = {st: i for i, st in enumerate(self.memo.states)}
            for sid in np.nonzero(old_R.any(axis=1))[0]:
                R[new_id[old_memo.states[sid]]] |= old_R[sid]
        self.R = R
        self._memo_dirty = False

    def _grow_W(self, W2: int) -> None:
        S = self.R.shape[0] if self.R is not None else 2
        if S * (1 << W2) > self.max_dense:
            raise _Overflow(f"dense config space {S}x{1 << W2}")
        if self.R is not None:
            R2 = np.zeros((self.R.shape[0], 1 << W2), bool)
            R2[:, :self.R.shape[1]] = self.R
            self.R = R2
        self.W = W2

    def _feed_native(self, types, procs, oids) -> None:
        W_new = self._mon.feed(types, procs, oids)
        if W_new == -1:
            raise _Overflow("double invoke")
        if W_new == -2:
            raise _Overflow(f"history needs > {self.max_slots} slots")
        if self.memo is None or self._memo_dirty:
            self._rebuild_memo()
        if W_new > self.W:
            self._grow_W(int(W_new))

    # -- ingestion ------------------------------------------------------------

    def feed(self, op: Op) -> None:
        self._buf.append(op)

    def feed_many(self, ops: List[Op]) -> None:
        self._buf.extend(ops)

    def _drain(self) -> None:
        if not self._buf:
            return
        ops, self._buf = self._buf, []
        n = len(ops)
        types = np.empty(n, np.int32)
        procs = np.empty(n, np.int64)
        oids = np.full(n, -1, np.int32)
        # locals for the per-op loop: this runs once per appended op
        # on the session hot path, where bound-method and attribute
        # re-lookup is a measurable fraction of the stage cost
        tcode_get = _TCODE.get
        oid = self._oid
        pkey = self._pkey
        live_inv = self._live_inv
        live_pop = live_inv.pop
        bind_ops = self._bind_ops
        bind_val = self._bind_val
        m = 0
        for op in ops:
            p = op.process
            if p == "nemesis":
                continue
            t = tcode_get(op.type)
            if t is None:
                continue
            if t == 0:
                # wildcard id: this op's crashed-at-invoke identity,
                # used only by the unsettled-tail alarm
                oids[m] = oid(op.f, op.value)
                live_inv[p] = (len(bind_ops), op)
                bind_ops.append(op)
            else:
                entry = live_pop(p, None)
                if entry is None:
                    continue            # completion without invoke
                bid, inv = entry
                if t == 1:              # ok: completion value wins
                    val = op.value if op.value is not None else inv.value
                    oids[m] = oid(inv.f, val)
                    bind_val[bid] = val
                elif t == 3:            # crashed: invoke value stands
                    oids[m] = oid(inv.f, inv.value)
                    bind_val[bid] = inv.value
            types[m] = t
            procs[m] = pkey(p)
            m += 1
        if m:
            self._feed_native(types[:m], procs[:m], oids[:m])

    # -- the walk -------------------------------------------------------------

    def _resolve_stragglers(self) -> None:
        """The run is over: every still-pending invocation resolves
        as crashed, making the final incremental verdict the exact
        full-history one. Shared with the device session engine
        (``serve.session.DeviceFrontierEngine``) so the two advance
        paths cannot drift."""
        if not self._live_inv:
            return
        items = list(self._live_inv.items())
        self._live_inv.clear()
        k = len(items)
        types = np.full(k, 3, np.int32)
        procs = np.empty(k, np.int64)
        oids = np.empty(k, np.int32)
        for i, (p, (bid, inv)) in enumerate(items):
            procs[i] = self._pkey(p)
            oids[i] = self._oid(inv.f, inv.value)
            self._bind_val[bid] = inv.value
        self._feed_native(types, procs, oids)

    def advance(self, run_over: bool = False) -> Optional[Dict[str, Any]]:
        if self.violation is not None:
            return self.violation
        self._drain()
        if run_over:
            self._resolve_stragglers()
        if self.memo is None:
            return None
        # one long-pending op blocks the whole settle queue; skip the
        # R pack/unpack round trip when advance would walk nothing
        _s, queued, _l, _w, front_ok = self._mon.stats()
        if queued == 0 or not front_ok:
            return None
        M = 1 << self.W
        words = _pack_words(self.R, M)
        walked, dead_bind = self._mon.advance(self.memo.table, words)
        self.R = _unpack_words(words, M)
        self.settled_returns += walked
        self.walked_events += walked
        if dead_bind >= 0:
            self.violation = self._violation_at(dead_bind)
        return self.violation

    def tail_alarm(self) -> Optional[Dict[str, Any]]:
        """Bounded unsettled-tail check from a COPY of the carried set,
        unresolved ops as crashed-at-invoke wildcards (sound
        over-approximation — an alarm is a real violation)."""
        if self.violation is not None or self.memo is None:
            return None
        self._drain()
        rows, slots, binds = self._mon.tail(self._TAIL_CAP, self.W)
        if len(slots) == 0:
            return None
        from jepsen_tpu.checkers import preproc_native
        words = _pack_words(self.R, 1 << self.W)   # a copy by packing
        dead = preproc_native.walk_dense(self.memo.table, words, self.W,
                                         slots, rows)
        if dead is not None and dead >= 0:
            self.violation = self._violation_at(int(binds[dead]))
        return self.violation

    def _violation_at(self, bid: int) -> Dict[str, Any]:
        inv = self._bind_ops[bid]
        op = inv.with_(type=OK, value=self._bind_val.get(bid, inv.value))
        return {"valid": False, "engine": "online-native",
                "op": op.to_dict(),
                "settled-returns": self.settled_returns}

    def in_flight(self) -> int:
        _settled, queued, live, _w, _f = self._mon.stats()
        return queued + live + len(self._buf)


class OnlineLinearizable:
    """Background prefix re-checker. Wire :meth:`observe` as the history
    observer (``core.History(observer=...)``), :meth:`start` /
    :meth:`stop` around the run, and pass ``on_violation`` to abort the
    test early (the runner sets its stop flag there)."""

    def __init__(self, model: Model, *,
                 interval_s: float = 1.0,
                 min_new_ops: int = 128,
                 mode: str = "incremental",
                 on_violation: Optional[Callable[[Dict[str, Any]], None]]
                 = None,
                 **checker_kw: Any):
        self.model = model
        self.interval_s = interval_s
        self.min_new_ops = min_new_ops
        self.mode = mode
        self.on_violation = on_violation
        self.checker_kw = checker_kw
        self._ops: List[Op] = []
        self._lock = threading.Lock()
        self._flush_lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._checked_upto = 0          # longest CONCLUSIVELY checked prefix
        self._inconclusive_tail = 0
        self._flushes = 0
        self._run_over = False
        self.violation: Optional[Dict[str, Any]] = None
        self._engine = None
        self._engine_cursor = 0
        if mode == "incremental":
            eng_kw = {k: checker_kw[k] for k in
                      ("max_states", "max_slots", "max_dense")
                      if k in checker_kw}
            # prefer the C++ streaming core (~6-8x the Python engine);
            # same semantics, differentially tested
            from jepsen_tpu.checkers import preproc_native
            if preproc_native.available():
                self._engine = NativeStreamEngine(model, **eng_kw)
            else:
                self._engine = IncrementalEngine(model, **eng_kw)

    # -- producer side (worker threads, via History observer) ---------------

    def observe(self, op: Op) -> None:
        with self._lock:
            self._ops.append(op)
        if len(self._ops) - self._checked_upto >= self.min_new_ops:
            self._wake.set()

    # -- checking ------------------------------------------------------------

    def flush(self) -> Optional[Dict[str, Any]]:
        """Check the current prefix; returns the violation dict once one
        is found (then sticky — no further work happens). Serialized: the
        monitor thread and a caller's stop() may both land here."""
        with self._flush_lock:
            return self._flush_locked()

    def _flush_locked(self) -> Optional[Dict[str, Any]]:
        if self.violation is not None:
            return self.violation
        if self._engine is not None:
            try:
                return self._flush_incremental()
            except _Overflow as e:
                # capacity decline, not a death: recorded as a route
                # decision (the engine-ladder discipline)
                from jepsen_tpu import obs
                obs.decision("online-incremental", "route",
                             cause=f"overflow:{type(e).__name__}")
                log.info("online check: dense state overflowed (%s); "
                         "falling back to prefix re-checking", e)
            except Exception as e:                      # noqa: BLE001
                from jepsen_tpu import obs
                obs.engine_fallback("online-incremental",
                                    type(e).__name__)
                log.warning("online incremental engine failed (%s); "
                            "falling back to prefix re-checking", e)
            # permanent fallback: the recheck path below re-verifies
            # everything from scratch, so nothing is lost
            self._engine = None
            self._checked_upto = 0
            self._inconclusive_tail = 0
        with self._lock:
            prefix = list(self._ops)
        if (len(prefix) <= self._checked_upto
                and not self._inconclusive_tail):
            return None
        from jepsen_tpu.checkers.facade import check_safe, linearizable

        kw = dict(self.checker_kw)
        if "algorithm" not in kw:
            # low-latency default: the C++ WGL engine has no per-shape
            # compile cost, so flushes keep up with fast op streams; a
            # time limit bounds its exponential worst case ("unknown"
            # flushes are retried at the next cadence tick). The device
            # engine remains the post-hoc source of truth.
            from jepsen_tpu.checkers import wgl_native
            if wgl_native.available():
                kw["algorithm"] = "wgl-native"
                kw.setdefault("time_limit", max(5.0, 5 * self.interval_s))
            else:
                kw["algorithm"] = "auto"
        checker = linearizable(self.model, **kw)
        res = check_safe(checker, None, prefix)
        self._flushes += 1
        if res.get("valid") is True:
            self._checked_upto = len(prefix)
            self._inconclusive_tail = 0
        elif res.get("valid") is False:
            self._checked_upto = len(prefix)
            self._inconclusive_tail = 0
            res["prefix-ops"] = len(prefix)
            res["detected-at-flush"] = self._flushes
            self.violation = res
            log.warning("online check: violation after %d ops (%s)",
                        len(prefix), res.get("op"))
            if self.on_violation is not None:
                try:
                    self.on_violation(res)
                # jtlint: ok fallback — on_violation notify garnish; the violation itself is recorded
                except Exception:                       # noqa: BLE001
                    pass
        else:
            # inconclusive (engine timeout / overflow): do NOT advance —
            # these ops are re-checked next flush, and result() must not
            # claim them verified
            self._inconclusive_tail = len(prefix) - self._checked_upto
        return self.violation

    def _flush_incremental(self) -> Optional[Dict[str, Any]]:
        eng = self._engine
        with self._lock:
            new = self._ops[self._engine_cursor:]
            self._engine_cursor = len(self._ops)
        if hasattr(eng, "feed_many"):
            eng.feed_many(new)
        else:
            for op in new:
                eng.feed(op)
        self._flushes += 1
        v = eng.advance(run_over=self._run_over)
        if v is None and not self._run_over:
            v = eng.tail_alarm()
        unsettled = eng.in_flight()
        self._checked_upto = max(0, self._engine_cursor - 2 * unsettled)
        if v is not None:
            v = dict(v)
            v["prefix-ops"] = self._engine_cursor
            v["detected-at-flush"] = self._flushes
            self.violation = v
            log.warning("online check: violation after %d ops (%s)",
                        self._engine_cursor, v.get("op"))
            if self.on_violation is not None:
                try:
                    self.on_violation(v)
                # jtlint: ok fallback — on_violation notify garnish; the violation itself is recorded
                except Exception:                       # noqa: BLE001
                    pass
        return self.violation

    # -- thread lifecycle ----------------------------------------------------

    def start(self) -> "OnlineLinearizable":
        import contextvars

        # run under a copy of the starter's context so obs records from
        # monitor flushes reach the enclosing run's capture scope
        ctx = contextvars.copy_context()
        self._thread = threading.Thread(target=lambda: ctx.run(self._loop),
                                        daemon=True,
                                        name="jepsen-online-check")
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set() and self.violation is None:
            self._wake.wait(self.interval_s)
            self._wake.clear()
            if self._stop.is_set():
                break
            try:
                self.flush()
            except Exception as e:                      # noqa: BLE001
                # the monitor thread keeps running and retries next
                # interval, but an unchecked window existed: recorded
                from jepsen_tpu import obs
                obs.checker_swallowed("online-flush",
                                      type(e).__name__)
                log.warning("online check flush failed: %s", e)

    def stop(self) -> Dict[str, Any]:
        """Stop the thread, run one final flush (with every straggler
        resolved as crashed — the run is over, so the incremental
        verdict becomes the exact full-history one), and return
        :meth:`result`."""
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(30)
        self._run_over = True
        try:
            self.flush()
        except Exception as e:                          # noqa: BLE001
            # result() below reports only what WAS verified; the
            # failed final flush is recorded, never silent
            from jepsen_tpu import obs
            obs.checker_swallowed("online-flush", type(e).__name__)
            log.warning("online check final flush failed: %s", e)
        return self.result()

    def result(self) -> Dict[str, Any]:
        if self.violation is not None:
            out = dict(self.violation)
            out["valid"] = False
            return out
        if self._engine is not None:
            out = {"valid": True, "mode": "incremental",
                   "ops-checked": self._engine_cursor,
                   "settled-returns": self._engine.settled_returns,
                   "flushes": self._flushes}
            if not self._run_over:
                unsettled = self._engine.in_flight()
                if unsettled:
                    out["in-flight-ops"] = unsettled
            return out
        out: Dict[str, Any] = {"valid": True,
                               "ops-checked": self._checked_upto,
                               "flushes": self._flushes}
        if self._inconclusive_tail:
            # the last flush(es) were inconclusive: the tail was never
            # verified, so the monitor's verdict is only "no violation
            # SEEN", not a clean bill
            out["valid"] = "unknown"
            out["unchecked-tail-ops"] = self._inconclusive_tail
        return out
