"""ctypes bridge to ``native/preproc.cpp`` — the C++ fast path for
event-stream preprocessing (slot assignment + returns projection).

:mod:`jepsen_tpu.checkers.events` calls :func:`assign_slots` /
:func:`returns_view` when the library builds, and falls back to its
pure-Python scans otherwise (same contract as
:mod:`jepsen_tpu.checkers.wgl_native` for the search itself).

Thread-safety contract: the stateless entry points (everything except
:class:`Monitor`, which owns mutable C++ state) take only caller-owned
buffers and keep no globals beyond the loaded library handle, and
ctypes releases the GIL for the call's duration — which is what lets
the streaming prep thread (``reach._dispatch_lockstep_stream``) run
:func:`build_keyed` per dispatch group while the main thread drives
jax, with the two genuinely overlapping.
"""
from __future__ import annotations

import ctypes
from typing import Optional, Tuple

import numpy as np

from jepsen_tpu.checkers._native_build import NativeLib

# every array parameter is declared void* and receives a raw buffer
# address (see _p): typed-POINTER marshaling builds a ctypes helper +
# cast object per argument (~3us each), and the per-append monitor
# path crosses this boundary enough times that typed pointers alone
# cost more than the C call they wrap. dtype/layout discipline moves
# to the call sites, which already allocate exact-dtype contiguous
# arrays.
_PTR = ctypes.c_void_p


def _declare(lib: ctypes.CDLL) -> None:
    lib.jt_assign_slots.restype = ctypes.c_int64
    lib.jt_assign_slots.argtypes = [
        ctypes.c_int64, _PTR, _PTR, ctypes.c_int64,
        ctypes.c_int32, _PTR]
    lib.jt_returns_view.restype = ctypes.c_int64
    lib.jt_returns_view.argtypes = [
        ctypes.c_int64, _PTR, _PTR, _PTR, _PTR,
        ctypes.c_int32, _PTR, _PTR, _PTR, _PTR]
    lib.jt_build_keyed.restype = ctypes.c_int64
    lib.jt_build_keyed.argtypes = [
        ctypes.c_int64, _PTR, _PTR, _PTR, _PTR, _PTR, _PTR,
        ctypes.c_int32, ctypes.c_int32,
        _PTR, _PTR, _PTR, _PTR, _PTR, _PTR]
    lib.jt_walk_dense.restype = ctypes.c_int64
    lib.jt_walk_dense.argtypes = [
        ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, _PTR,
        ctypes.c_int32, _PTR, ctypes.c_int64, _PTR, _PTR]
    lib.jt_gen_history.restype = ctypes.c_int64
    lib.jt_gen_history.argtypes = [
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, _PTR, _PTR, _PTR, _PTR]
    lib.jt_mon_new.restype = ctypes.c_void_p
    lib.jt_mon_new.argtypes = [ctypes.c_int32]
    lib.jt_mon_free.restype = None
    lib.jt_mon_free.argtypes = [ctypes.c_void_p]
    lib.jt_mon_feed.restype = ctypes.c_int64
    lib.jt_mon_feed.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, _PTR, _PTR, _PTR]
    lib.jt_mon_advance.restype = ctypes.c_int64
    lib.jt_mon_advance.argtypes = [
        ctypes.c_void_p, _PTR, ctypes.c_int32, ctypes.c_int32,
        _PTR, ctypes.c_int64, _PTR]
    lib.jt_mon_tail.restype = ctypes.c_int64
    lib.jt_mon_tail.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, _PTR, _PTR, _PTR]
    lib.jt_mon_drain.restype = ctypes.c_int64
    lib.jt_mon_drain.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, _PTR, _PTR, _PTR]
    lib.jt_mon_stats.restype = ctypes.c_int64
    lib.jt_mon_stats.argtypes = [ctypes.c_void_p, _PTR]
    lib.jt_mon_live.restype = ctypes.c_int64
    lib.jt_mon_live.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, _PTR, _PTR]


_NATIVE = NativeLib("preproc.cpp", "libjepsen_preproc.so", _declare)
_load = _NATIVE.load
build_error = _NATIVE.build_error


def available() -> bool:
    return _NATIVE.available()


def _p(a: np.ndarray) -> int:
    # raw buffer address for a void* parameter: ~3x cheaper than
    # a.ctypes.data_as(POINTER(...)) on the per-append monitor path
    return a.__array_interface__["data"][0]


def assign_slots(kind: np.ndarray, entry: np.ndarray, n_entries: int,
                 max_slots: int) -> Optional[Tuple[np.ndarray, int]]:
    """Returns ``(slot[E], W)``; None if the native lib is unavailable.
    Raises the same overflow condition as the Python path by returning
    ``W = -1`` sentinel (callers translate to ConcurrencyOverflow)."""
    lib = _load()
    if lib is None:
        return None
    E = len(kind)
    kind = np.ascontiguousarray(kind, np.int32)
    entry = np.ascontiguousarray(entry, np.int32)
    out = np.empty(E, np.int32)
    W = int(lib.jt_assign_slots(E, _p(kind), _p(entry),
                                int(n_entries), int(max_slots), _p(out)))
    return out, W


def returns_view(kind: np.ndarray, slot: np.ndarray, opid: np.ndarray,
                 entry: np.ndarray, W: int, n_events: int
                 ) -> Optional[Tuple[np.ndarray, np.ndarray,
                                     np.ndarray, np.ndarray, int]]:
    """Returns ``(ret_slot, slot_ops, ret_event, ret_entry, R)``; None
    if the native lib is unavailable."""
    lib = _load()
    if lib is None:
        return None
    kind = np.ascontiguousarray(kind[:n_events], np.int32)
    slot = np.ascontiguousarray(slot[:n_events], np.int32)
    opid = np.ascontiguousarray(opid[:n_events], np.int32)
    entry = np.ascontiguousarray(entry[:n_events], np.int32)
    n_ret_max = int(np.sum(kind == 1))
    ret_slot = np.empty(n_ret_max, np.int32)
    slot_ops = np.empty((n_ret_max, max(W, 1)), np.int32)
    ret_event = np.empty(n_ret_max, np.int32)
    ret_entry = np.empty(n_ret_max, np.int32)
    R = int(lib.jt_returns_view(
        n_events, _p(kind), _p(slot), _p(opid), _p(entry),
        max(W, 1), _p(ret_slot), _p(slot_ops), _p(ret_event),
        _p(ret_entry)))
    return ret_slot[:R], slot_ops[:R], ret_event[:R], ret_entry[:R], R


def build_keyed(entry_off: np.ndarray, inv_rank: np.ndarray,
                ret_rank: np.ndarray, opid: np.ndarray,
                crashed: np.ndarray, noop_op: np.ndarray,
                max_slots: int, w_cap: int):
    """Batched per-key event building (``jt_build_keyed``): one native
    call builds every key's slotted return stream into flat arrays.
    Returns ``(ret_slot, slot_ops[:, :w_cap], pend, key_W, key_R,
    ret_entry, R_total)`` or None when the native lib is unavailable —
    callers fall back to the per-key Python/ctypes pipeline."""
    lib = _load()
    if lib is None:
        return None
    K = len(entry_off) - 1
    N = int(entry_off[-1])
    entry_off = np.ascontiguousarray(entry_off, np.int64)
    inv_rank = np.ascontiguousarray(inv_rank, np.int32)
    ret_rank = np.ascontiguousarray(ret_rank, np.int32)
    opid = np.ascontiguousarray(opid, np.int32)
    crashed = np.ascontiguousarray(crashed, np.uint8)
    noop_op = np.ascontiguousarray(noop_op, np.uint8)
    ret_slot = np.empty(N, np.int32)
    slot_ops = np.empty((N, max(w_cap, 1)), np.int32)
    pend = np.empty(N, np.int32)
    key_W = np.empty(K, np.int32)
    key_R = np.empty(K, np.int32)
    ret_entry = np.empty(N, np.int32)
    R = int(lib.jt_build_keyed(
        K, _p(entry_off), _p(inv_rank), _p(ret_rank),
        _p(opid), _p(crashed),
        _p(noop_op), int(max_slots), int(max(w_cap, 1)),
        _p(ret_slot), _p(slot_ops), _p(pend), _p(key_W), _p(key_R),
        _p(ret_entry)))
    return (ret_slot[:R], slot_ops[:R], pend[:R], key_W, key_R,
            ret_entry[:R], R)


def gen_history(seed: int, n_ops: int, processes: int, values: int,
                kind: int):
    """Native benchmark-history simulation (``jt_gen_history``):
    returns ``(inv_ev, ret_ev, opid, proc, count)`` per surviving
    entry (in return order), or None when the lib is unavailable."""
    lib = _load()
    if lib is None:
        return None
    inv_ev = np.empty(n_ops, np.int32)
    ret_ev = np.empty(n_ops, np.int32)
    opid = np.empty(n_ops, np.int32)
    proc = np.empty(n_ops, np.int32)
    count = int(lib.jt_gen_history(
        int(seed), int(n_ops), int(processes), int(values), int(kind),
        _p(inv_ev), _p(ret_ev), _p(opid), _p(proc)))
    return (inv_ev[:count], ret_ev[:count], opid[:count], proc[:count],
            count)


class Monitor:
    """Handle to the C++ streaming-monitor core (``jt_mon_*``): the
    per-op bookkeeping of the incremental linearizability monitor —
    slot assignment, settle-queue snapshots, settled-returns walking —
    fed in per-flush batches. Owned by
    :class:`jepsen_tpu.checkers.online.NativeStreamEngine`."""

    def __init__(self, max_slots: int):
        lib = _load()
        if lib is None:
            raise RuntimeError("native lib unavailable")
        self._lib = lib
        self._h = ctypes.c_void_p(lib.jt_mon_new(int(max_slots)))
        # stats() runs several times per session append; a reusable
        # out-buffer with a pre-resolved address and a pre-bound C
        # entry point halves its cost (safe: the owning engine is
        # lock-serialized per session)
        self._stats_fn = lib.jt_mon_stats
        self._stats_out = np.zeros(5, np.int64)
        self._stats_ptr = _p(self._stats_out)

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            self._lib.jt_mon_free(h)

    def feed(self, types: np.ndarray, procs: np.ndarray,
             oids: np.ndarray) -> int:
        """Returns the (possibly grown) W; negative = overflow (the
        caller falls back permanently)."""
        types = np.ascontiguousarray(types, np.int32)
        procs = np.ascontiguousarray(procs, np.int64)
        oids = np.ascontiguousarray(oids, np.int32)
        return int(self._lib.jt_mon_feed(
            self._h, len(types), _p(types),
            _p(procs), _p(oids)))

    def advance(self, T: np.ndarray, R_words: np.ndarray
                ) -> Tuple[int, int]:
        """Walk every settleable queued return; ``R_words`` u64
        [S, n_words] mutated in place. Returns ``(walked, dead_bind)``
        with ``dead_bind = -1`` when the set survived."""
        S, n_ops = T.shape
        T = np.ascontiguousarray(T, np.int32)
        assert R_words.dtype == np.uint64 and R_words.flags.c_contiguous
        dead = np.full(1, -1, np.int32)
        walked = int(self._lib.jt_mon_advance(
            self._h, _p(T), S, n_ops,
            _p(R_words), R_words.shape[1], _p(dead)))
        return walked, int(dead[0])

    def drain(self, cap: int, W: int):
        """Pop every currently-settleable queued return WITHOUT
        walking it: ``(rows[n, W], slots[n], binds[n])``. The
        device-resident session engine walks the drained block on the
        accelerator (the settle discipline stays the monitor's; only
        the walk moves) and owns death handling — the native settled
        counter is advanced by the drain itself."""
        rows = np.empty((max(cap, 1), max(W, 1)), np.int32)
        slots = np.empty(max(cap, 1), np.int32)
        binds = np.empty(max(cap, 1), np.int32)
        n = int(self._lib.jt_mon_drain(self._h, cap, _p(rows),
                                       _p(slots), _p(binds)))
        return rows[:n], slots[:n], binds[:n]

    def tail(self, K: int, W: int):
        """First ≤K unsettled items as ``(rows[K, W], slots, binds)``
        with unresolved members as crashed-at-invoke wildcards."""
        rows = np.empty((K, max(W, 1)), np.int32)
        slots = np.empty(K, np.int32)
        binds = np.empty(K, np.int32)
        n = int(self._lib.jt_mon_tail(self._h, K, _p(rows), _p(slots),
                                      _p(binds)))
        return rows[:n], slots[:n], binds[:n]

    def stats(self) -> Tuple[int, int, int, int, int]:
        """(settled_returns, queued_returns, live_invocations, W,
        front_settleable)."""
        out = self._stats_out
        self._stats_fn(self._h, self._stats_ptr)
        return (int(out[0]), int(out[1]), int(out[2]), int(out[3]),
                int(out[4]))

    def live(self, cap: int):
        """(procs, bind_indices) of still-pending invocations."""
        procs = np.empty(cap, np.int64)
        binds = np.empty(cap, np.int32)
        n = int(self._lib.jt_mon_live(
            self._h, cap, _p(procs), _p(binds)))
        return procs[:n], binds[:n]


def walk_dense(T: np.ndarray, R_words: np.ndarray, W: int,
               ret_slot: np.ndarray, rows: np.ndarray) -> Optional[int]:
    """Bit-packed dense returns walk (``jt_walk_dense``): ``T``
    i32[S, O] transition table, ``R_words`` u64[S, n_words] the
    bit-packed config set (MUTATED in place), ``rows`` i32[L, W] the
    pending ops per return. Returns the first dead return index (-1 if
    the set survived), or None when the native lib is unavailable."""
    lib = _load()
    if lib is None:
        return None
    S, n_ops = T.shape
    L = len(ret_slot)
    n_words = R_words.shape[1]
    T = np.ascontiguousarray(T, np.int32)
    ret_slot = np.ascontiguousarray(ret_slot, np.int32)
    rows = np.ascontiguousarray(rows, np.int32)
    assert R_words.dtype == np.uint64 and R_words.flags.c_contiguous
    return int(lib.jt_walk_dense(
        S, int(W), n_words, _p(T), n_ops,
        _p(R_words), L, _p(ret_slot), _p(rows)))
