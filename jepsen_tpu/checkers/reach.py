"""Dense-reachability linearizability engine — the TPU-native search.

Upstream analogue: ``knossos/src/knossos/linear.clj`` (Lowe's just-in-time
linearization) raced against ``knossos/src/knossos/wgl.clj`` by
``knossos/src/knossos/competition.clj`` (SURVEY.md §2.2, §3.2). This is NOT a
port of either: where the upstream maintains an explicit, heap-allocated
*set* of configurations ⟨model-state, linearized-pending-ops⟩ and dies when
it explodes, this engine observes that the config space is the product
``states × 2**W`` (W = max concurrently-pending ops, small in real
histories) and represents the *entire reachable set* as one dense boolean
tensor ``R[state, mask]``. The search becomes a single ``lax.while_loop``
over the history's event stream:

- **fire** (linearize a pending op): a vectorized transition applied to all
  configs at once — a gather through the memoized transition table plus a
  scatter-or into the bit-set half of the mask axis. Between events, ops may
  linearize in any order; the engine runs fire passes to a fixpoint
  (monotone, so ≤ pending+1 passes), which covers every interleaving.
- **invoke**: records the op in its slot (a loop-carried ``i32[W]`` map).
- **return**: configs that never linearized the returning op are killed
  (boolean mask); its slot bit is cleared and freed. An empty ``R`` is a
  linearizability violation at exactly that event — the same minimal
  evidence knossos reports.

Closure passes are only needed immediately before return events: a fire
deferred across intervening invokes is still legal (pending sets only grow
between returns), so the reachable set at each return is unchanged — this
is Lowe's just-in-time idea expressed as dataflow.

Crashed (``info``) ops hold a slot forever and may fire at any later point
or never — both covered by the optional fire. Crashed ops whose transitions
are no-ops everywhere are dropped in preprocessing (:mod:`.events`).

Scaling axes (SURVEY.md §2.4):

- **Per-key batch** (``jepsen.independent``): :func:`check_many` vmaps the
  walk over keys — embarrassingly parallel, shard the key axis over the
  device mesh.
- **History-length parallelism** (the sequence-parallel analogue):
  :func:`check_chunked` splits the event stream into chunks and runs the
  walk *batched over all D = states·2**W basis configs* per chunk —
  computing each chunk's boolean transfer matrix in parallel — then
  composes the matrices. Chunks shard across devices
  (:mod:`jepsen_tpu.parallel`); composition is a tiny boolean matmul chain.

Exact, not probabilistic: unlike a hashed memo table (fingerprint
collisions could silently declare a non-linearizable history valid), the
dense set cannot produce false verdicts.
"""
from __future__ import annotations

import functools
import logging
import os
import threading
import time as _time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from jepsen_tpu import history as h
from jepsen_tpu import obs
from jepsen_tpu.checkers import dispatch_core
from jepsen_tpu.checkers import events as ev
from jepsen_tpu.checkers import transfer
from jepsen_tpu.models import Model
from jepsen_tpu.models.memo import (
    Memo, StateExplosion, memo as build_memo, memo_ops)
from jepsen_tpu.op import Op
from jepsen_tpu.util import hashable


class DenseOverflow(RuntimeError):
    """The dense config tensor would exceed the configured budget; callers
    should fall back to another engine."""


# -- device program ----------------------------------------------------------

def _fire_pass(R, slot_op, T):
    """One pass of 'linearize one more pending op', vectorized over all
    configs: for each slot j (static unroll), configs with bit j clear fire
    the slot's op through the transition table into the bit-set half."""
    import jax.numpy as jnp

    S, M = R.shape
    W = slot_op.shape[0]
    n_cols = T.shape[1]
    for j in range(W):
        o = jnp.where(slot_op[j] < 0, n_cols - 1, slot_op[j])
        col = T[:, o]                          # i32[S]; -1 = illegal
        tgt = jnp.where(col < 0, S, col)       # row S = discard
        Rr = R.reshape(S, M >> (j + 1), 2, 1 << j)
        lo = Rr[:, :, 0, :]                    # configs with bit j clear
        fired = jnp.zeros((S + 1,) + lo.shape[1:], jnp.bool_)
        fired = fired.at[tgt].max(lo)
        Rr = Rr.at[:, :, 1, :].set(Rr[:, :, 1, :] | fired[:S])
        R = Rr.reshape(S, M)
    return R


def _closure(R, slot_op, T):
    """Fixpoint of :func:`_fire_pass` — covers every linearization order of
    any subset of pending ops (monotone ⇒ converges in ≤ pending+1 passes)."""
    from jax import lax
    import jax.numpy as jnp

    R1 = _fire_pass(R, slot_op, T)

    def cond(c):
        prev, cur = c
        return jnp.any(prev != cur)

    def body(c):
        _, cur = c
        return cur, _fire_pass(cur, slot_op, T)

    _, Rf = lax.while_loop(cond, body, (R, R1))
    return Rf


def _project_return(R, j):
    """Return of the op in (dynamic) slot ``j``: keep configs that fired it,
    clearing bit j so the slot can be reused."""
    import jax.numpy as jnp

    S, M = R.shape
    idx = jnp.arange(M)
    src = idx | (1 << j)
    clear = ((idx >> j) & 1) == 0
    return jnp.where(clear[None, :], R[:, src], False)


def _walk(T, kind, slot, opid, R0, slot_op0):
    """Drive the event stream over the dense config set. Returns
    ``(ptr, R, alive)``; ``alive=False`` means the set emptied at event
    ``ptr-1`` (a violation witness)."""
    from jax import lax
    import jax.numpy as jnp

    E = kind.shape[0]

    def cond(c):
        ptr, R, slot_op, alive = c
        return (ptr < E) & alive

    def body(c):
        ptr, R, slot_op, alive = c
        k, j, o = kind[ptr], slot[ptr], opid[ptr]

        def on_invoke(R, slot_op):
            return R, slot_op.at[j].set(o)

        def on_return(R, slot_op):
            Rc = _closure(R, slot_op, T)
            return _project_return(Rc, j), slot_op.at[j].set(-1)

        def on_pad(R, slot_op):
            return R, slot_op

        R, slot_op = lax.switch(k, [on_invoke, on_return, on_pad], R, slot_op)
        return ptr + 1, R, slot_op, jnp.any(R)

    init = (jnp.int32(0), R0, slot_op0, jnp.any(R0))
    ptr, R, _, alive = lax.while_loop(cond, body, init)
    return ptr, R, alive


# -- fast path: returns-only walk with matrix transitions --------------------
#
# Invoke events never change the reachable set — they only update the
# slot→op map, which is statically known host-side — so the device loop
# executes RETURN events only (half the iterations), with the pending map
# gathered per return from a precomputed array. Firing is expressed as a
# contraction against per-op boolean transition matrices P[o][s, s'] =
# (T[s, o] == s') instead of scatters: Rx gathers the bit-clear half of
# every slot's mask axis at once (a static XOR column permutation), one
# einsum applies all W slot transitions, and a static upper bound of W
# fire passes replaces the dynamic fixpoint (at most W pending ops can
# linearize between returns, and passes are monotone).

def _ret_step(P, xor_cols, bitmask, R, j, ops_row):
    """One return event: W static fire passes (at most W pending ops can
    linearize between returns; passes are monotone so W passes reach the
    fixpoint), then projection on the returning slot. ``j < 0`` =
    padding (identity)."""
    import jax.numpy as jnp

    W, M = xor_cols.shape
    n_ops_pad = P.shape[0] - 1
    G = P[jnp.where(ops_row < 0, n_ops_pad, ops_row)]       # [W, S, S]
    for _ in range(W):
        Rx = R[:, xor_cols]                                 # [S, W, M]
        contrib = jnp.einsum("sjm,jst->tjm", Rx.astype(jnp.float32), G)
        add = ((contrib > 0.5) & bitmask[None]).any(axis=1)
        R = R | add
    jj = jnp.maximum(j, 0)
    idx = jnp.arange(M)
    bit = jnp.int32(1) << jj
    src = idx | bit
    clear = (idx & bit) == 0
    Rp = jnp.where(clear[None, :], R[:, src], False)
    return jnp.where(j >= 0, Rp, R)


def _walk_returns(P, xor_cols, bitmask, ret_slot, slot_ops, R0,
                  unroll: int = 8):
    """Drive return events over the dense config set. ``P`` f32[O+1,S,S]
    (row O = sentinel, all-zero); ``xor_cols`` i32[W,M] = m^(1<<j);
    ``bitmask`` bool[W,M] = bit j set in m. Processes ``unroll`` returns
    per loop iteration to amortize while-loop overhead (callers pad Rn to
    a multiple). Returns ``(ptr, R, alive)``: when dead, the set emptied
    at some return in ``[ptr-unroll, ptr)``."""
    import jax.numpy as jnp
    from jax import lax

    Rn = ret_slot.shape[0]

    def cond(c):
        i, R, alive, _ = c
        return (i < Rn) & alive

    def body(c):
        i, R, _, _ = c
        R_block = R                     # carried so callers can refine the
        for k in range(unroll):         # exact dead return within a block
            R = _ret_step(P, xor_cols, bitmask, R,
                          ret_slot[i + k], slot_ops[i + k])
        return i + unroll, R, jnp.any(R), R_block

    init = (jnp.int32(0), R0, jnp.any(R0), R0)
    ptr, R, alive, R_block = lax.while_loop(cond, body, init)
    return ptr, R, alive, R_block


def _walk_returns_scan(P, xor_cols, bitmask, ret_slot, slot_ops, R0):
    """Scan variant (no early exit) for the basis-batched chunk walk —
    returns only the final R."""
    from jax import lax

    def step(R, inp):
        j, ops_row = inp
        return _ret_step(P, xor_cols, bitmask, R, j, ops_row), None

    R, _ = lax.scan(step, R0, (ret_slot, slot_ops))
    return R


def _build_P(memo: Memo, S_pad: int, O_pad: Optional[int] = None
             ) -> np.ndarray:
    """Per-op transition matrices P[o][s, s'] = (table[s, o] == s'), f32,
    with an all-zero sentinel row at index O_pad."""
    O = memo.n_ops if O_pad is None else O_pad
    P = np.zeros((O + 1, S_pad, S_pad), np.float32)
    s = np.arange(memo.n_states)
    for o in range(memo.n_ops):
        col = memo.table[:, o]
        ok = col >= 0
        P[o, s[ok], col[ok]] = 1.0
    return P


def _xor_bitmask(W: int, M: int):
    j = np.arange(W)[:, None]
    m = np.arange(M)[None, :]
    return ((m ^ (1 << j)).astype(np.int32),
            ((m >> j) & 1).astype(bool))


_UNROLL = 8


@functools.cache
def _jitted_walk_returns():
    import jax
    return jax.jit(functools.partial(_walk_returns, unroll=_UNROLL))


@functools.cache
def _jitted_walk_returns_u1():
    import jax
    return jax.jit(functools.partial(_walk_returns, unroll=1))


@functools.cache
def _jitted_walk_returns_batch():
    """vmap over keys: per-key P, return streams, and config sets."""
    import jax
    return jax.jit(jax.vmap(
        functools.partial(_walk_returns, unroll=_UNROLL),
        in_axes=(0, None, None, 0, 0, 0)))


@functools.cache
def _jitted_walk_returns_batch_shared():
    """vmap over keys with a SHARED transition-matrix tensor — the common
    case where every key runs the same workload over the same op alphabet
    (uniform ``independent`` tests): no per-key P gather, better fusion."""
    import jax
    return jax.jit(jax.vmap(
        functools.partial(_walk_returns, unroll=_UNROLL),
        in_axes=(None, None, None, 0, 0, None)))


def _refine_dead(P, xor_cols, bitmask, rs: "ev.ReturnStream",
                 ptr: int, R_block) -> int:
    """Exact dead return index: the unrolled walk died somewhere in
    ``[ptr-unroll, ptr)``; re-walk that block one return at a time from
    the carried block-start config set."""
    import jax.numpy as jnp

    W = xor_cols.shape[0]
    start = max(0, int(ptr) - _UNROLL)
    tail_slot = np.full(_UNROLL, -1, np.int32)
    tail_ops = np.full((_UNROLL, W), -1, np.int32)
    seg = slice(start, min(int(ptr), rs.R))
    n_seg = seg.stop - seg.start
    tail_slot[:n_seg] = rs.ret_slot[seg]
    tail_ops[:n_seg] = rs.slot_ops[seg]
    ptr1, _, alive, _ = _jitted_walk_returns_u1()(
        P, xor_cols, bitmask, jnp.asarray(tail_slot),
        jnp.asarray(tail_ops), R_block)
    if bool(alive):                     # shouldn't happen; be conservative
        return int(rs.ret_event[min(int(ptr), rs.n_returns) - 1])
    return int(rs.ret_event[start + int(ptr1) - 1])


@functools.cache
def _jitted_basis_returns():
    """vmap over (chunk, basis-config) for history-length parallelism."""
    import jax
    inner = jax.vmap(_walk_returns_scan,
                     in_axes=(None, None, None, None, None, 0))
    outer = jax.vmap(inner, in_axes=(None, None, None, 0, 0, 0))
    return jax.jit(outer)


# -- carried-frontier advance (streaming check sessions) ---------------------
#
# A long-lived check session (jepsen_tpu/serve/session.py) keeps its
# reachable-config frontier R ON DEVICE across appends: each append
# block's settled returns advance the carried set in ONE dispatch.
# The dense body's carry is DONATED so XLA recycles the [S, M] buffer
# in place (the transfer-diet donation applied to a frontier that
# lives for the whole session, not just a pipeline); the word-packed
# body's carry is a few machine words and is deliberately NOT donated
# (see _jitted_word_walk). Only the per-block (ret_slot, slot_ops)
# operands cross the wire per append — narrow ints on the standard
# diet — and the verdict fetch is the walk's one alive bool.
#
# Two kernel bodies share the carry protocol:
#
# - **Word-packed** (M <= 64, i.e. W <= 6 — the repo-default workload
#   shape): the mask axis lives in ONE machine word per state
#   (uint32/uint64 [S]), a fire pass is pure bitwise algebra
#   (`(R & ~colmask_j) << 2^j`, OR-scattered through the transition
#   column), and the whole scan body fuses into straight-line code —
#   measured ~1 µs/return on XLA:CPU, 33x the dense einsum step whose
#   gather/einsum chain is thunk-dispatch-bound there (a first
#   instance of ROADMAP item 3's bit-parallel kernel bodies). Death
#   indices are exact per step (no unroll-window refine).
# - **Dense** [S, M] einsum walk (`_walk_returns`): the wide-geometry
#   fallback, the same program the post-hoc engines run.

@functools.cache
def _jitted_advance_frontier():
    """Donated-carry unrolled returns walk: the dense session append
    path. The carried set is argument 5 (R0); donating it makes the
    in-place advance free — the returned R aliases the carry's
    buffer."""
    import jax
    return jax.jit(functools.partial(_walk_returns, unroll=_UNROLL),
                   donate_argnums=(5,))


def _word_masks(W: int, dtype) -> Tuple[np.ndarray, np.ndarray]:
    """Per-slot mask-axis constants of the word-packed walk:
    ``cmask[j]`` has bit m set iff mask m has bit j set; ``shift[j]``
    is ``2^j`` (firing slot j moves config bit m to m | 1<<j, a left
    shift by 2^j on the bit-j-clear half)."""
    M = 1 << W
    m = np.arange(M)
    cmask = np.array(
        [sum(1 << int(x) for x in m[(m >> j) & 1 == 1])
         for j in range(W)], dtype)
    shift = np.array([1 << j for j in range(W)], np.uint32)
    return cmask, shift


def _word_walk(Tpad, R0, ret_slot, slot_ops):
    """Word-packed returns walk: ``Tpad`` i32[S, O+1] (col O = -1
    sentinel), ``R0`` uint32/uint64[S] (bit m of R[s] = config (s, m)
    reachable), blocks of (ret_slot, slot_ops) as in
    :func:`_walk_returns`. Returns ``(R, any_dead, first_dead)`` with
    the EXACT step index of the first death (pads — ret_slot -1 —
    cannot kill a live set). Fire semantics are `_ret_step`'s: W
    simultaneous-slot passes reach the closure between returns,
    projection keeps the fired half of the returning slot."""
    import jax.numpy as jnp
    from jax import lax

    S = Tpad.shape[0]
    O1 = Tpad.shape[1] - 1
    W = slot_ops.shape[1]
    dt = R0.dtype
    cmask_np, shift_np = _word_masks(W, dt)
    cmask = jnp.asarray(cmask_np)
    mult = (jnp.asarray(np.uint64(1) if dt == jnp.uint64
                        else np.uint32(1)).astype(dt)
            << jnp.asarray(shift_np).astype(dt))
    s_idx = jnp.arange(S)

    def step(R, inp):
        j, ops_row = inp
        o = jnp.where(ops_row < 0, O1, ops_row)
        tcols = Tpad[:, o]                       # [S, W]
        tgt = jnp.where(tcols < 0, S, tcols)     # row S = discard
        for _ in range(W):
            lo = R[:, None] & (~cmask)[None, :]
            shifted = lo * mult[None, :]         # << 2^j, bitexact
            oh = s_idx[:, None, None] == tgt[None, :, :]
            contrib = jnp.where(oh, shifted[None, :, :],
                                jnp.zeros((), dt))
            fired = lax.reduce(contrib, np.zeros((), dt)[()],
                               lax.bitwise_or, (1, 2))
            R = R | fired
        jj = jnp.maximum(j, 0)
        # projection: keep the bit-j-set half, clearing the bit — an
        # exact right shift by 2^j (unsigned // by a power of two)
        proj = (R & cmask[jj]) // mult[jj]
        R = jnp.where(j >= 0, proj, R)
        return R, R.max() == jnp.zeros((), dt)[()]

    R, deads = lax.scan(step, R0, (ret_slot, slot_ops))
    return R, deads.any(), deads.argmax()


@functools.cache
def _jitted_word_walk():
    # deliberately NOT donated: the word-packed carry is a few machine
    # words (S * 4 bytes), so donation saves nothing — and donating it
    # was measured to CORRUPT the carry under concurrent jax activity
    # on the CPU client (garbage bits appearing in the aliased output
    # while another thread dispatches; reproduced ~30%/run by a
    # facade-hammer thread, never without donation — the regression
    # test in tests/test_session.py pins this). The DENSE carry keeps
    # its donation: that buffer is the one worth recycling, and the
    # dense path is unaffected under the same hammer.
    import jax
    return jax.jit(_word_walk)


class FrontierCarry:
    """Device-resident reachable-config frontier for ONE session
    geometry ``(S, M=2^W)``: holds the carried R and the
    device-cached transition operand. A geometry change (memo
    rebuild, slot growth) discards the carry — the session engine
    re-encodes host-side and seeds a fresh one.

    The walk body is the word-packed kernel whenever ``M <= 64``
    (one uint32/uint64 word per state; exact per-step death) and the
    dense ``_walk_returns`` einsum program otherwise. ``advance``
    pads each block to a power-of-two length (identity steps:
    ``ret_slot = -1``) so a session compiles log2-many walk
    geometries, not one per block size. ``JEPSEN_TPU_NO_WORD_WALK=1``
    forces the dense body (differential tests pin the two
    bit-identical)."""

    _MIN_BLOCK = 64

    def __init__(self, P_np: Optional[np.ndarray], W: int, M: int,
                 R0_host: np.ndarray,
                 table: Optional[np.ndarray] = None,
                 p_build=None) -> None:
        import jax
        import jax.numpy as jnp

        from jepsen_tpu.checkers import reach_word

        self.W, self.M = int(W), int(M)
        self.S = int(R0_host.shape[0])
        self.advanced_returns = 0
        # one uint32 word per state for M <= 32; uint32 word VECTORS
        # (reach_word, ceil(M/32) words) beyond — so W > 5 sessions
        # run word-packed WITHOUT x64 mode (the former uint64 body,
        # which jax silently downcasts outside x64, is retired)
        self._nw = 1 if self.M <= 32 else reach_word.n_words(self.M)
        S_t = int(table.shape[0]) if table is not None else self.S
        multi_ok = (self.M <= 32
                    or reach_word.admits(S_t, self.W, self.M))
        self.words = (table is not None and multi_ok
                      and not os.environ.get(
                          "JEPSEN_TPU_NO_WORD_WALK"))
        if self.words:
            # word-packed body: the transition TABLE (with a -1
            # sentinel column for pad slots) is the only operand —
            # the O(O*S^2) dense P tensor is never materialized on
            # this path (callers pass it lazily via p_build). The
            # column axis pads to a power-of-two bucket: extra -1
            # columns are never indexed by real ops (their ids stay
            # below the true O) and pad slots hit the LAST column
            # (also -1), so the walk is bit-identical — but session
            # alphabets that grow at different rates land in the SAME
            # walk geometry, which is what makes mega-batch grouping
            # converge (and caps the daemon's compiled-walk count at
            # log2-many table widths per S)
            O1_pad = reach_word._pad_pow2(int(table.shape[1]) + 1, 8)
            Tpad = np.concatenate(
                [table,
                 -np.ones((S_t, O1_pad - int(table.shape[1])),
                          table.dtype)],
                axis=1).astype(np.int32)
            # plain device_put, NOT transfer.cached_put: the host
            # array is rebuilt per carry seed, so the identity-keyed
            # cache could never hit — it would only pin dead copies
            self._T = jax.device_put(Tpad)
            # host mirror for the mega gather: the table never
            # changes after seeding, so a mega-group can stack lane
            # tables with one numpy concat + ONE device put instead
            # of per-lane device stacking (reach_word
            # .advance_frontiers_mega)
            self._T_host = Tpad
            # the [S, M] bool seed packs to S word vectors — fewer
            # wire bytes than even the bit-packed dense seed
            if self._nw == 1:
                words = _pack_frontier_words(R0_host[:S_t], self.M,
                                             np.uint32)
            else:
                words = reach_word.pack_words(
                    np.ascontiguousarray(R0_host[:S_t], bool))
            transfer.count_put(int(words.nbytes),
                               int(R0_host.size * 4))
            self._R = jax.device_put(words)
            self.S = S_t
            return
        if P_np is None:
            P_np = p_build()
        xor_np, bit_np = _xor_bitmask(self.W, self.M)
        self._xor = jnp.asarray(xor_np)
        self._bit = jnp.asarray(bit_np)
        # plain device_put (see the word branch: per-seed host arrays
        # cannot hit the identity-keyed operand cache)
        self._P = jax.device_put(P_np)
        # seed crosses bit-packed (8 configs/byte) and unpacks where
        # bandwidth is free; the advance itself ships no config set
        if transfer.packed_enabled():
            packed = transfer.pack_bool(R0_host)
            transfer.count_put(int(packed.nbytes),
                               int(R0_host.size * 4))
            self._R = _jitted_unpack_seed()(
                jnp.asarray(packed), self.S, self.M)
        else:
            transfer.count_put(int(R0_host.size),
                               int(R0_host.size * 4))
            self._R = jax.device_put(
                np.ascontiguousarray(R0_host, bool))

    def _pad_block(self, ret_slot: np.ndarray, slot_ops: np.ndarray):
        n = len(ret_slot)
        n_pad = max(self._MIN_BLOCK, _next_pow2(n))
        rs = np.full(n_pad, -1, np.int32)
        so = np.full((n_pad, self.W), -1, np.int32)
        rs[:n] = ret_slot
        so[:n] = slot_ops
        return rs, so

    def advance(self, ret_slot: np.ndarray,
                slot_ops: np.ndarray) -> int:
        """Advance the carried frontier through one settled block.
        Returns the exact index of the first dead return, or -1 when
        the set survived. On death the carry is left at the walk's
        final (empty) set — death is terminal for a session."""
        import jax.numpy as jnp

        n = len(ret_slot)
        if n == 0:
            return -1
        rs, so = self._pad_block(ret_slot, slot_ops)
        nb = int(so.nbytes + rs.nbytes)
        transfer.count_put(nb, int((rs.size + so.size) * 4))
        if self.words:
            R, any_dead, first = self._word_fn()(
                self._T, self._R, jnp.asarray(rs), jnp.asarray(so))
            self._R = R
            if not bool(any_dead):
                self.advanced_returns += n
                return -1
            dead = min(int(first), n - 1)
            self.advanced_returns += dead + 1
            return dead
        ptr, R, alive, R_block = _jitted_advance_frontier()(
            self._P, self._xor, self._bit, jnp.asarray(rs),
            jnp.asarray(so), self._R)
        self._R = R
        if bool(alive):
            self.advanced_returns += n
            return -1
        dead = self._refine(rs, so, int(ptr), R_block, n)
        self.advanced_returns += dead + 1
        return dead

    def _refine(self, rs, so, ptr: int, R_block, n: int) -> int:
        """Exact dead index of the dense body: u1 re-walk of the
        dying unroll window from the carried block-start set
        (identity pads cannot die, so the refined index always lands
        on a real return)."""
        import jax.numpy as jnp
        start = max(0, ptr - _UNROLL)
        ptr1, _, alive1, _ = _jitted_walk_returns_u1()(
            self._P, self._xor, self._bit,
            jnp.asarray(rs[start:start + _UNROLL]),
            jnp.asarray(so[start:start + _UNROLL]), R_block)
        dead = (start + int(ptr1) - 1) if not bool(alive1) \
            else min(ptr, n) - 1
        return min(dead, n - 1)

    def probe(self, ret_slot: np.ndarray,
              slot_ops: np.ndarray) -> int:
        """Tail-alarm walk from the carried set WITHOUT touching it
        (the plain non-donating jit): returns the exact dead index or
        -1. Sound over-approximation semantics are the caller's (it
        passes unresolved ops as crashed wildcards)."""
        import jax.numpy as jnp

        n = len(ret_slot)
        if n == 0:
            return -1
        rs, so = self._pad_block(ret_slot, slot_ops)
        if self.words:
            _R, any_dead, first = self._word_fn()(
                self._T, self._R, jnp.asarray(rs), jnp.asarray(so))
            if not bool(any_dead):
                return -1
            return min(int(first), n - 1)
        ptr, _R, alive, R_block = _jitted_walk_returns()(
            self._P, self._xor, self._bit, jnp.asarray(rs),
            jnp.asarray(so), self._R)
        if bool(alive):
            return -1
        return self._refine(rs, so, int(ptr), R_block, n)

    def _word_fn(self):
        """The jitted word-walk body: the single-word kernel for
        M <= 32 (the battle-tested PR-10 program), the multi-word
        ``reach_word`` kernel beyond — same (T, R, rs, so) ->
        (R, any_dead, first) contract, neither donated."""
        if self._nw == 1:
            return _jitted_word_walk()
        from jepsen_tpu.checkers import reach_word
        return reach_word._jitted_walk_words()

    def fetch(self) -> np.ndarray:
        """The carried set back on host as bool [S, M] (geometry
        re-encode before a memo rebuild / slot growth; counted as an
        eager fetch)."""
        obs.count("fetch.eager")
        if self.words:
            if self._nw > 1:
                from jepsen_tpu.checkers import reach_word
                return reach_word.unpack_words(np.asarray(self._R),
                                               self.M)
            return _unpack_frontier_words(np.asarray(self._R), self.M)
        return np.asarray(self._R).astype(bool)


def _pack_frontier_words(R: np.ndarray, M: int, dt) -> np.ndarray:
    """bool [S, M] -> one word per state (bit m = config (s, m))."""
    S = R.shape[0]
    out = np.zeros(S, dt)
    for j in range(M):
        out |= (R[:, j].astype(dt) << dt(j))
    return out


def _unpack_frontier_words(words: np.ndarray, M: int) -> np.ndarray:
    m = np.arange(M).astype(words.dtype)
    return ((words[:, None] >> m[None, :]) & 1).astype(bool)


@functools.cache
def _jitted_unpack_seed():
    """Bit-packed seed -> dense bool [S, M] on device (static S/M)."""
    import jax
    import jax.numpy as jnp

    def unpack(packed, S: int, M: int):
        return jnp.unpackbits(packed, count=S * M).reshape(S, M) \
                  .astype(jnp.bool_)

    return jax.jit(unpack, static_argnums=(1, 2))


# fast path applies while the fire-pass intermediate [S, W, M] AND the
# per-op transition-matrix tensor [O+1, S, S] stay small; state-rich /
# op-rich histories keep the event walk (gather through the flat table)
_FAST_MAX_ELEMS = 1 << 22
_FAST_MAX_P = 1 << 24


def _use_pallas() -> bool:
    """Single-history returns walks run as one fused Pallas kernel on TPU
    (:mod:`.reach_pallas`) — the XLA while-loop version dispatches ~25
    tiny ops per return and is ~2.4x slower at the headline config. Set
    ``JEPSEN_TPU_NO_PALLAS=1`` to force the XLA path."""
    import os
    if os.environ.get("JEPSEN_TPU_NO_PALLAS"):
        return False
    try:
        import jax
        return jax.devices()[0].platform == "tpu"
    # jtlint: ok fallback — capability probe: False just routes away from the fast path
    except Exception:                                   # noqa: BLE001
        return False


def _fast_ok(S_pad: int, W: int, M: int, n_ops: int) -> bool:
    return (S_pad * max(W, 1) * M <= _FAST_MAX_ELEMS
            and (n_ops + 1) * S_pad * S_pad <= _FAST_MAX_P)


# the pallas kernel keeps P plus three [M, S] f32 buffers wholly in VMEM
# (~16 MiB/core); beyond this budget the XLA walk (P in HBM) takes over
_PALLAS_MAX_VMEM_BYTES = 8 << 20

# below this many returns the XLA walk was measured to win on an earlier
# remote device: the pallas call's fixed cost (kernel dispatch +
# SMEM-result round-trips) exceeded the XLA walk's per-return advantage.
# Unmeasured on the chip.
_PALLAS_MIN_RETURNS = 8192


def _pallas_fits(S_pad: int, M: int, n_ops: int) -> bool:
    vmem = 4 * ((n_ops + 1) * S_pad * S_pad + 3 * M * S_pad)
    return vmem <= _PALLAS_MAX_VMEM_BYTES


def _fetch(x) -> np.ndarray:
    """Host copy of a device array that may be sharded across processes
    in a multi-host run (a plain ``np.asarray`` raises on non-addressable
    shards); every process receives the full array."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


@functools.cache
def _warn_pallas_failed_once(err: str) -> None:
    """Surface each distinct Pallas failure once — a permanent kernel
    breakage silently degrading every check to the slower XLA walk should
    not be invisible."""
    logging.getLogger("jepsen.reach").warning(
        "pallas returns-walk failed (%s); falling back to the XLA walk",
        err)


def _warn_pallas_failed(err: str) -> None:
    """Every Pallas → fallback degradation bumps
    ``reach.pallas_fallback`` and lands in the obs ledger (the log
    line stays once-per-distinct-error); fuzz/soak summaries and the
    bench ``obs`` sub-object surface the counter, so a kernel breakage
    that silently costs throughput is visible without log greps."""
    obs.count("reach.pallas_fallback")
    obs.decision("pallas", "fallback", cause=err[:200])
    _warn_pallas_failed_once(err)


@functools.cache
def _ensure_persistent_caches() -> None:
    """Once per process, at the first engine entry: turn on jax's
    persistent compilation cache
    (:func:`jepsen_tpu.store.enable_compilation_cache`) so warm starts
    skip XLA recompiles of every previously-seen kernel geometry.
    Best-effort and opt-out (``JEPSEN_TPU_NO_PERSIST=1``); the
    disk-backed memo tier (:func:`_disk_memo_get`) shares the
    switch."""
    try:
        from jepsen_tpu import store
        store.enable_compilation_cache()
    # jtlint: ok fallback — persistence is best-effort; the check's verdict is unaffected
    except Exception:                                   # noqa: BLE001
        pass                            # persistence must never fail a check


@functools.cache
def _jitted_walk():
    import jax
    return jax.jit(_walk)


@functools.cache
def _jitted_walk_batch():
    """vmap over a leading key axis on every operand (per-key transition
    tables, event streams, and config sets)."""
    import jax
    return jax.jit(jax.vmap(_walk))


# -- host orchestration ------------------------------------------------------

def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


def _raised_from_jax(e: BaseException) -> bool:
    """True when the exception is jax/jaxlib's — either by class (e.g.
    XlaRuntimeError) or by raise site (jax raises builtin ValueError/
    RuntimeError for mesh-shape and OOM failures, which must keep the
    graceful fallback while our own programming errors surface).

    For NON-jax exception classes, a ``jepsen_tpu`` frame BELOW the
    first jax frame *in the traceback* means jax re-entered our code
    (tracing a kernel/walk body) and the raise is ours — a genuine
    repo bug that must surface, not silently degrade to a fallback
    engine. The below-jax test keys ONLY on traceback-observed jax
    frames: the traceback always begins with our own caller frames
    (the function holding the ``try``), which are ABOVE jax, not
    below. Exceptions of jax's own classes (XlaRuntimeError & co) are
    environmental by definition and keep the fallback even when our
    traced body appears mid-traceback."""
    if (type(e).__module__ or "").startswith(("jax", "jaxlib")):
        return True
    tb = e.__traceback__
    tb_jax_seen = False
    ours_below_jax = False
    while tb is not None:
        mod = tb.tb_frame.f_globals.get("__name__", "")
        if mod.startswith(("jax", "jaxlib")):
            tb_jax_seen = True
        elif tb_jax_seen and mod.startswith("jepsen_tpu"):
            ours_below_jax = True   # our code raised inside jax tracing
        tb = tb.tb_next
    return tb_jax_seen and not ours_below_jax


def _bucket(x: int, grain: int = 8) -> int:
    """Round up to ``m·2^e`` with 8 mantissa steps per octave (≤12.5%
    padding), then to a multiple of ``grain``. Compared to next-pow-2
    (worst case +100% padded work) this keeps the jit shape-cache small
    (≤8 shapes per octave) while nearly eliminating padding overhead —
    on a 100k-op history the returns walk is the whole check, so pow-2
    padding alone cost ~40% of wall-clock."""
    x = max(int(x), 1)
    if x <= 8 * grain:
        return -(-x // grain) * grain
    e = x.bit_length() - 4              # mantissa in [8, 16]
    m = -(-x >> e)
    return -(-(m << e) // grain) * grain


# memo tables depend only on (model, alphabet-as-a-SET, cap) — identical
# across the keys of a uniform `independent` workload, where rebuilding
# the BFS per key dominated host time (~40% of a 1024-key warm check).
# Alphabets are canonicalized by sorting (per-key id assignment is
# occurrence-ordered, so two keys running the same workload usually
# disagree on order); on every hit the cached table's columns are
# permuted back to the history's local op-id order (state ids are
# arbitrary labels, so no other remap is needed). Bounded by entry
# count AND per-entry bytes —
# big memos (state-rich models) are not worth pinning for the process
# lifetime.
_MEMO_CACHE: "Dict[Any, Memo]" = {}
_MEMO_CACHE_LOCK = threading.Lock()
_MEMO_CACHE_MAX = 512
_MEMO_CACHE_MAX_ENTRY_BYTES = 1 << 20
# `states` pins one Model object per reachable state — for state-rich
# models that dwarfs the table, so cap the state count too
_MEMO_CACHE_MAX_ENTRY_STATES = 4096


def _op_sort_key(t):
    return (repr(t[0]), repr(t[1]))


def _cached_memo(model: Model, packed: h.PackedHistory,
                 max_states: int) -> Memo:
    """Memo for ``packed``'s alphabet, cached across histories. The
    cache entry is built on the SORTED alphabet (hit regardless of
    per-history occurrence order); on return its table columns are
    permuted back to this history's local op-id order and its
    ``distinct_ops`` are THIS history's ops — callers and failure
    witnesses never see another history's op objects."""
    keys = list(h.op_keys_of(packed))
    try:
        order = sorted(range(len(keys)), key=lambda i: _op_sort_key(keys[i]))
        sig = (model, max_states, tuple(keys[i] for i in order))
        hash(sig)
    # jtlint: ok fallback — unhashable model: cache bypass, the memo is simply rebuilt
    except TypeError:                   # unhashable model/values: no cache
        return build_memo(model, packed, max_states=max_states)
    with _MEMO_CACHE_LOCK:
        m = _MEMO_CACHE.get(sig)
        if m is not None:
            # LRU, not insertion order: a hit moves the entry to the
            # MRU end, so a hot memo inserted early outlives cold
            # recent ones when _cache_put evicts from the front
            _MEMO_CACHE.pop(sig)
            _MEMO_CACHE[sig] = m
    if m is None:
        obs.count("memo_cache.miss")
        # superset fallback: random workloads give every key a slightly
        # different SUBSET of one underlying alphabet (a 100-op cas
        # history hits ~30 of 36 possible ops), so exact-signature
        # lookups almost always miss across keys. check_many seeds the
        # union-alphabet memo up front for precisely this hit. The
        # projection is ALSO inserted into the exact cache (canonical
        # order) so repeated checks over the same alphabet — the online
        # monitor's flushes, competition re-runs — go back to dict hits.
        m2 = _project_from_seeds(model, keys, max_states,
                                 packed.distinct_ops)
        if m2 is not None:
            inv_lut = np.empty(len(keys), np.int32)
            for col, i in enumerate(order):
                inv_lut[col] = i
            canon = Memo(
                table=np.ascontiguousarray(m2.table[:, inv_lut]),
                states=m2.states,
                distinct_ops=tuple(packed.distinct_ops[i]
                                   for i in order),
                initial=m2.initial)
            _cache_put(sig, canon)
            return m2
        canonical_ops = tuple(packed.distinct_ops[i] for i in order)
        m = _disk_memo_get(sig, canonical_ops)
        if m is None:
            m = memo_ops(model, canonical_ops, max_states=max_states)
            _disk_memo_put(sig, m)
        _cache_put(sig, m)
    else:
        obs.count("memo_cache.hit")
    # local op id i lives in canonical column lut[i]
    lut = np.empty(len(keys), np.int32)
    for col, i in enumerate(order):
        lut[i] = col
    return Memo(table=np.ascontiguousarray(m.table[:, lut]),
                states=m.states, distinct_ops=packed.distinct_ops,
                initial=m.initial)


def _cache_put(sig, m: Memo) -> None:
    """Insert into the exact-signature cache, applying the size gates
    (big memos are cheap to rebuild relative to their footprint and are
    not worth pinning) and the shared evict-on-full policy. The facade
    races engines on threads and the online monitor flushes from its
    own — lookup/insert/eviction stay lock-guarded."""
    if (m.table.nbytes > _MEMO_CACHE_MAX_ENTRY_BYTES
            or m.n_states > _MEMO_CACHE_MAX_ENTRY_STATES):
        return
    with _MEMO_CACHE_LOCK:
        if len(_MEMO_CACHE) >= _MEMO_CACHE_MAX:
            # front = LRU end (hits re-append in _cached_memo)
            _MEMO_CACHE.pop(next(iter(_MEMO_CACHE)), None)
            obs.count("memo_cache.evict")
        _MEMO_CACHE[sig] = m


# -- disk tier below _MEMO_CACHE (ISSUE 3 persistent caches) ----------------
#
# Memo tables depend only on (model, alphabet, cap): a fresh process
# re-checking the same workload re-ran the BFS for every alphabet it had
# already enumerated. The disk tier persists the canonical-order memo
# under the store dir (same opt-out as the compilation cache),
# keyed by a digest of the model's class+repr, the cap, and the sorted
# alphabet — so a changed model signature can never serve a stale table.
# Same size gates as _cache_put: big memos are cheap to rebuild relative
# to their footprint.

_DISK_MEMO_VERSION = 1


def _disk_memo_path(sig) -> Optional[Tuple[str, str]]:
    """(path, signature-repr) for ``sig``'s disk entry, or None when
    persistence is off. The repr is stored inside the pickle and
    compared on load — a digest collision or a model whose repr
    changed meaning can never alias. A model whose repr is the default
    address-stamped ``<C object at 0x...>`` has no stable cross-process
    signature: the tier is skipped for it (every process would mint a
    fresh orphan entry that can never hit)."""
    from jepsen_tpu import store
    root = store.persist_root()
    if root is None:
        return None
    import hashlib
    model, max_states, keys = sig
    model_rep = repr(model)
    if model_rep.endswith(f"at {hex(id(model))}>"):
        return None                     # default object repr: unstable
    rep = repr((_DISK_MEMO_VERSION, type(model).__module__,
                type(model).__qualname__, model_rep, max_states, keys))
    name = hashlib.sha256(rep.encode()).hexdigest()[:40] + ".memo.pkl"
    return os.path.join(root, "memo", name), rep


def _disk_memo_get(sig, canonical_ops: Tuple[Op, ...]) -> Optional[Memo]:
    """Load ``sig``'s memo from the disk tier. The stored table is in
    canonical (sorted-alphabet) order — identical to what the in-memory
    build would produce — and ``distinct_ops`` are replaced with THIS
    history's op objects, mirroring the superset-projection care. The
    stored MODEL OBJECT is compared by equality against the requester's
    — the same relation the BFS itself keys states on — so a custom
    ``__repr__`` that omits a behavior-affecting field (repr collision)
    still cannot serve a stale table."""
    import pickle
    pr = _disk_memo_path(sig)
    if pr is None:
        return None
    path, rep = pr
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
        if (payload.get("sig") != rep
                or type(payload.get("model")) is not type(sig[0])
                or payload.get("model") != sig[0]):
            raise ValueError("memo signature mismatch")
        m = payload["memo"]
        m = Memo(table=m.table, states=m.states,
                 distinct_ops=canonical_ops, initial=m.initial)
    except FileNotFoundError:
        obs.count("memo_cache.disk.miss")
        return None
    except Exception:                                   # noqa: BLE001
        obs.count("memo_cache.disk.invalid")
        try:
            os.unlink(path)             # corrupt/stale entry: drop it
        # jtlint: ok fallback — absent/unreadable disk entry is a cache miss, counted by the caller
        except OSError:
            pass
        return None
    obs.count("memo_cache.disk.hit")
    return m


# entry-count cap for the disk memo dir: a fuzz/soak campaign mints a
# fresh alphabet (→ a fresh entry) per random workload, and nothing
# else ever deletes them — evict oldest-mtime past the cap on store
_DISK_MEMO_MAX_ENTRIES = 512


def _disk_memo_put(sig, m: Memo) -> None:
    """Best-effort insert into the disk tier (atomic rename; a full or
    read-only disk must never fail a check). Bounded: past
    ``_DISK_MEMO_MAX_ENTRIES`` the oldest entries are evicted, so a
    long soak cannot grow the tier monotonically."""
    import pickle
    if (m.table.nbytes > _MEMO_CACHE_MAX_ENTRY_BYTES
            or m.n_states > _MEMO_CACHE_MAX_ENTRY_STATES):
        return
    pr = _disk_memo_path(sig)
    if pr is None:
        return
    path, rep = pr
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump({"sig": rep, "model": sig[0], "memo": m}, f)
        os.replace(tmp, path)
        obs.count("memo_cache.disk.store")
        d = os.path.dirname(path)
        names = [n for n in os.listdir(d) if n.endswith(".memo.pkl")]
        if len(names) > _DISK_MEMO_MAX_ENTRIES:
            by_age = sorted(
                names, key=lambda n: os.path.getmtime(os.path.join(d, n)))
            for n in by_age[:len(names) - _DISK_MEMO_MAX_ENTRIES]:
                try:
                    os.unlink(os.path.join(d, n))
                    obs.count("memo_cache.disk.evict")
                # jtlint: ok fallback — best-effort cache store/evict; misses are counted on read
                except OSError:
                    pass
    # jtlint: ok fallback — best-effort cache store/evict; misses are counted on read
    except Exception:                                   # noqa: BLE001
        pass


# superset seeds: a few union-alphabet memos with precomputed
# key -> column maps, consulted on exact-cache misses. Bounded in both
# count and state size so a pathological giant entry can't bloat every
# subsequent small check; failed unions are remembered so callers don't
# re-run a doomed BFS per call.
_SUPERSET_SEEDS: Dict[Any, Any] = {}
_SUPERSET_SEEDS_FAILED: set = set()
_SUPERSET_SEEDS_MAX = 8
_SUPERSET_MAX_STATES = 1024


def _project_from_seeds(model: Model, keys: Sequence[Any],
                        max_states: int,
                        distinct_ops: Tuple[Op, ...]) -> Optional[Memo]:
    """Build a memo for ``keys`` (local op order) by column-projecting a
    seeded SUPERSET memo, then restricting to the states actually
    reachable under these ops — the projected memo is identical to a
    fresh BFS up to state relabeling, so per-key ``S_pad`` and the
    dense/kernel capacity gates are unchanged by the cache route."""
    with _MEMO_CACHE_LOCK:
        seeds = list(_SUPERSET_SEEDS.values())
    for m2, model2, max2, col_of in seeds:
        if (model2 == model and max2 == max_states
                and all(k in col_of for k in keys)):
            lut = np.fromiter((col_of[k] for k in keys),
                              np.int32, max(len(keys), 0))
            T = m2.table[:, lut] if len(keys) else \
                np.zeros((m2.n_states, 0), np.int32)
            # reachable restriction: BFS from the initial state over
            # the projected columns (pure NumPy, O(S·O) int ops)
            reach_mask = np.zeros(m2.n_states, bool)
            reach_mask[m2.initial] = True
            frontier = np.array([m2.initial])
            while frontier.size:
                nxt = np.unique(T[frontier])
                nxt = nxt[nxt >= 0]
                fresh = nxt[~reach_mask[nxt]]
                reach_mask[fresh] = True
                frontier = fresh
            keep = np.nonzero(reach_mask)[0]            # sorted
            new_id = np.full(m2.n_states + 1, -1, np.int32)
            new_id[keep] = np.arange(len(keep), dtype=np.int32)
            Tk = T[keep]
            Tk = np.where(Tk >= 0, new_id[Tk], -1)
            return Memo(table=np.ascontiguousarray(Tk),
                        states=tuple(m2.states[i] for i in keep),
                        distinct_ops=distinct_ops,
                        initial=int(new_id[m2.initial]))
    return None


def _memo_for_ops(model: Model, ops: Tuple[Op, ...],
                  max_states: int) -> Memo:
    """Memo over an explicit op tuple, served from the superset seeds
    when one covers it (column projection, no BFS) — the union memo in
    ``_keyed_operands`` is usually exactly the seeded one."""
    try:
        keys = [(op.f, hashable(op.value)) for op in ops]
        m = _project_from_seeds(model, keys, max_states, ops)
        if m is not None:
            return m
    # jtlint: ok fallback — unhashable values: superset seeding skipped, exact path intact
    except TypeError:
        pass
    return memo_ops(model, ops, max_states=max_states)


def _seed_union_memo(model: Model,
                     packed_list: Sequence[h.PackedHistory],
                     max_states: int) -> None:
    """Intern ONE memo over the union of every key's op alphabet so the
    per-key ``_cached_memo`` lookups hit its superset projection instead
    of each running their own BFS (4096 uniform keys: ~4082 BFS runs →
    1). Best-effort: state explosion or unhashables just skip — and the
    BFS is capped at the seed size bound (an oversized union aborts at
    ~1k states, once, instead of enumerating ``max_states`` per call)."""
    union: Dict[Any, Op] = {}
    try:
        for packed in packed_list:
            for key, op in zip(h.op_keys_of(packed),
                               packed.distinct_ops):
                union.setdefault(key, op)
        keys = list(union)
        order = sorted(range(len(keys)),
                       key=lambda i: _op_sort_key(keys[i]))
        sig = (model, max_states, tuple(keys[i] for i in order))
        hash(sig)
        with _MEMO_CACHE_LOCK:
            if sig in _SUPERSET_SEEDS or sig in _SUPERSET_SEEDS_FAILED:
                return
        ops = tuple(union[keys[i]] for i in order)
        m = memo_ops(model, ops,
                     max_states=min(max_states, _SUPERSET_MAX_STATES))
    # jtlint: ok fallback — decline tracked in _SUPERSET_SEEDS_FAILED; per-key path decides
    except StateExplosion:
        with _MEMO_CACHE_LOCK:
            if len(_SUPERSET_SEEDS_FAILED) < 64:
                _SUPERSET_SEEDS_FAILED.add(sig)
        return                      # per-key path handles these fine
    # jtlint: ok fallback — unhashable signature: no seed, per-key path decides
    except TypeError:
        return
    col_of = {k: i for i, k in enumerate(keys[i] for i in order)}
    with _MEMO_CACHE_LOCK:
        if len(_SUPERSET_SEEDS) >= _SUPERSET_SEEDS_MAX:
            _SUPERSET_SEEDS.pop(next(iter(_SUPERSET_SEEDS)), None)
        _SUPERSET_SEEDS[sig] = (m, model, max_states, col_of)


def _pad_table(memo: Memo, S_pad: int, O_pad: int) -> np.ndarray:
    """Transition table padded to [S_pad, O_pad+1]; everything outside the
    real region (including the sentinel last column for opid=-1) is -1."""
    S, O = memo.table.shape
    T = np.full((S_pad, O_pad + 1), -1, np.int32)
    T[:S, :O] = memo.table
    return T


def _prep(model: Model, packed: h.PackedHistory, *,
          max_states: int, max_slots: int, max_dense: int,
          e_bucket: int = 64, memo: Optional[Memo] = None):
    """Shared host-side pipeline: memo table + slotted event stream, with
    the event axis padded to :func:`_bucket` sizes (8 per octave) so jit
    compilations are reused across histories of similar size. A caller
    may inject a prebuilt ``memo`` (the restricted-product transactional
    checker builds one over only the jointly-reachable product states —
    :mod:`jepsen_tpu.checkers.decompose`)."""
    if memo is None:
        memo = _cached_memo(model, packed, max_states)
    stream = ev.build(packed, memo, max_slots=max_slots)
    S = memo.n_states
    S_pad = max(2, _next_pow2(S))
    M = 1 << stream.W
    if S_pad * M > max_dense:
        raise DenseOverflow(
            f"dense config space {S_pad}x{M} exceeds budget {max_dense}")
    O_pad = max(2, _next_pow2(memo.n_ops))
    E_pad = max(e_bucket, _bucket(stream.E, e_bucket))
    stream = ev.pad(stream, E_pad)
    T = _pad_table(memo, S_pad, O_pad)
    return memo, stream, T, S_pad, M


def _result_valid(engine: str, stream: ev.EventStream, memo: Memo,
                  elapsed: float) -> Dict[str, Any]:
    return {"valid": True, "engine": engine, "events": stream.n_events,
            "slots": stream.W, "states": memo.n_states,
            "dropped-crashed-noops": stream.n_dropped_crashed,
            "time-s": elapsed}


def _result_invalid(engine: str, stream: ev.EventStream, memo: Memo,
                    packed: h.PackedHistory, dead_event: int,
                    elapsed: float) -> Dict[str, Any]:
    entry = packed.entries[int(stream.entry[dead_event])]
    linearized = int(np.sum(
        stream.kind[:dead_event] == ev.KIND_RETURN))
    return {"valid": False, "engine": engine, "op": entry.op.to_dict(),
            "max-linearized": linearized, "events": stream.n_events,
            "slots": stream.W, "states": memo.n_states,
            "dead-event": int(dead_event), "time-s": elapsed}


def _final_configs(memo: Memo, rs: "ev.ReturnStream", P_np: np.ndarray,
                   S_pad: int, M: int, W: int, dead_ret: int,
                   limit: int = 16) -> List[Dict[str, Any]]:
    """Decode the configurations that survived up to (but not through)
    the dead return — the analogue of knossos's ``:final-paths``: each
    entry is a reachable model state plus the pending ops it has already
    linearized. Together they show every way the search tried to order
    the window, and that none admits the failing return.

    The prefix and P's op axis pad to powers of two (identity returns;
    zero rows past the real ops, so the last row stays the all-zero
    sentinel): a batch of failed keys, each in its own geometry,
    compiles the walk a handful of times instead of once per key."""
    import jax.numpy as jnp

    xor_cols, bitmask = _xor_bitmask(W, M)
    L = _next_pow2(max(dead_ret, _UNROLL))
    O_pad = _next_pow2(P_np.shape[0])
    P_np = np.pad(P_np, ((0, O_pad - P_np.shape[0]), (0, 0), (0, 0)))
    prefix = ev.pad_returns(
        ev.ReturnStream(ret_slot=rs.ret_slot[:dead_ret],
                        slot_ops=rs.slot_ops[:dead_ret],
                        ret_event=rs.ret_event[:dead_ret],
                        ret_entry=rs.ret_entry[:dead_ret],
                        W=W, n_returns=dead_ret), L)
    R0 = jnp.zeros((S_pad, M), jnp.bool_).at[0, 0].set(True)
    _, R, _, _ = _jitted_walk_returns()(
        jnp.asarray(P_np), jnp.asarray(xor_cols), jnp.asarray(bitmask),
        jnp.asarray(prefix.ret_slot), jnp.asarray(prefix.slot_ops), R0)
    alive = np.argwhere(np.asarray(R))
    pending = rs.slot_ops[dead_ret]
    out = []
    for s, mask in alive[:limit]:
        lin = [str(memo.distinct_ops[pending[j]])
               for j in range(W)
               if (mask >> j) & 1 and pending[j] >= 0]
        out.append({"model": str(memo.states[s]),
                    "linearized-pending": lin})
    return out


def _attach_witness(out: Dict[str, Any], memo: Memo, rs, P_np, S_pad, M,
                    W, dead_ret: int, packed: h.PackedHistory) -> None:
    """Enrich an invalid verdict with knossos-style failure evidence:
    ``final-configs`` (:func:`_final_configs`) and ``previous-ok`` (the
    last successfully linearized return before the failing one)."""
    try:
        out["final-configs"] = _final_configs(
            memo, rs, P_np, S_pad, M, W, dead_ret)
        if dead_ret > 0:
            prev = packed.entries[int(rs.ret_entry[dead_ret - 1])]
            out["previous-ok"] = prev.op.to_dict()
    # jtlint: ok fallback — witness evidence is best-effort garnish on a decided verdict
    except Exception:                                   # noqa: BLE001
        pass                            # evidence is best-effort garnish


def _attach_witness_slow(out: Dict[str, Any], memo: Memo,
                         stream: ev.EventStream, T, S_pad: int, M: int,
                         W: int, dead_event: int,
                         packed: h.PackedHistory,
                         limit: int = 16) -> None:
    """Witness evidence for the slow event-walk path (taken when the
    per-return matrix form doesn't fit): re-walk the event prefix up to
    the failing event to recover the surviving config set, decode it
    knossos-style (``final-configs``), and name the last successfully
    linearized return (``previous-ok``). The slot→op pending map at the
    failing event is replayed host-side (it is statically determined by
    the stream)."""
    import jax.numpy as jnp

    try:
        E_pad = max(64, _bucket(max(dead_event, 1), 64))
        kind = np.full(E_pad, ev.KIND_PAD, np.int32)
        slot = np.zeros(E_pad, np.int32)
        opid = np.full(E_pad, -1, np.int32)
        kind[:dead_event] = stream.kind[:dead_event]
        slot[:dead_event] = stream.slot[:dead_event]
        opid[:dead_event] = stream.opid[:dead_event]
        R0 = jnp.zeros((S_pad, M), jnp.bool_).at[0, 0].set(True)
        slot_op0 = jnp.full((W,), -1, jnp.int32)
        _, R_prev, _ = _jitted_walk()(
            jnp.asarray(T), jnp.asarray(kind), jnp.asarray(slot),
            jnp.asarray(opid), R0, slot_op0)
        # pending map at the failing event, replayed host-side
        pending = np.full(W, -1, np.int64)
        for e in range(dead_event):
            if stream.kind[e] == ev.KIND_INVOKE:
                pending[stream.slot[e]] = stream.opid[e]
            elif stream.kind[e] == ev.KIND_RETURN:
                pending[stream.slot[e]] = -1
        alive = np.argwhere(np.asarray(R_prev))
        configs = []
        for s, mask in alive[:limit]:
            lin = [str(memo.distinct_ops[pending[j]])
                   for j in range(W)
                   if (int(mask) >> j) & 1 and pending[j] >= 0]
            configs.append({"model": str(memo.states[s]),
                            "linearized-pending": lin})
        out["final-configs"] = configs
        rets = np.nonzero(
            stream.kind[:dead_event] == ev.KIND_RETURN)[0]
        if len(rets):
            prev = packed.entries[int(stream.entry[int(rets[-1])])]
            out["previous-ok"] = prev.op.to_dict()
    # jtlint: ok fallback — witness evidence is best-effort garnish on a decided verdict
    except Exception:                                   # noqa: BLE001
        pass                            # evidence is best-effort garnish


def check(model: Model, history: Sequence[Op], *,
          max_states: int = 100_000, max_slots: int = 20,
          max_dense: int = 1 << 22,
          should_abort=None) -> Dict[str, Any]:
    """Check one history on device. Raises :class:`DenseOverflow`,
    :class:`~jepsen_tpu.checkers.events.ConcurrencyOverflow`, or
    :class:`~jepsen_tpu.models.memo.StateExplosion` when the history does
    not fit this engine — the :func:`jepsen_tpu.checkers.linearizable`
    facade catches these and falls back to the CPU search. With
    ``should_abort`` the walk is dispatched in bounded segments and
    yields ``valid == "unknown"`` when the hook fires (upstream
    ``knossos.search`` abort semantics)."""
    packed = h.pack(history)
    return check_packed(model, packed, max_states=max_states,
                        max_slots=max_slots, max_dense=max_dense,
                        should_abort=should_abort)


# XLA-walk segment size under an abort hook (the lane kernel has its
# own, reach_lane._ABORT_SEG)
_ABORT_SEG = 32768

_ABORTED = {"valid": "unknown", "cause": "aborted", "engine": "reach"}


def _posthoc_body(S: int, W: int, M: int, n_returns: int) -> str:
    """Kernel-body selection for the single-history post-hoc walk:
    the persisted autotune table first (a ``walk`` winner recorded by
    ``tools/ablate_lane.py --bodies`` / ``bench.py``), then the
    ``JEPSEN_TPU_WORD_POSTHOC=1`` force, else the dense/pallas chain
    as before. Returns ``"word"`` or ``"dense"``; ``"word"`` is only
    answered where the word body admits the geometry."""
    from jepsen_tpu.checkers import reach_word
    if not (reach_word.enabled() and reach_word.admits(S, W, M)):
        return "dense"
    if os.environ.get("JEPSEN_TPU_WORD_POSTHOC"):
        return "word"
    from jepsen_tpu.checkers import autotune
    w = autotune.winner("walk",
                        autotune.walk_key(S, W, M, n_returns))
    return w if w in ("word", "dense") else "dense"


def check_packed(model: Model, packed: h.PackedHistory, *,
                 max_states: int = 100_000, max_slots: int = 20,
                 max_dense: int = 1 << 22,
                 should_abort=None,
                 memo: Optional[Memo] = None) -> Dict[str, Any]:
    import jax.numpy as jnp

    _ensure_persistent_caches()
    t0 = _time.monotonic()
    if packed.n == 0 or packed.n_ok == 0:
        return {"valid": True, "engine": "reach", "events": 0,
                "time-s": 0.0}
    with obs.span("reach.prep", ops=packed.n):
        memo, stream, T, S_pad, M = _prep(
            model, packed, max_states=max_states, max_slots=max_slots,
            max_dense=max_dense, memo=memo)
    W = max(stream.W, 1)
    if _fast_ok(S_pad, W, M, memo.n_ops):
        rs = ev.returns_view(stream)
        if (should_abort is None
                and _posthoc_body(memo.n_states, W, M,
                                  rs.n_returns) == "word"):
            # word-packed kernel body (reach_word): the mask axis as
            # uint32 word vectors per state, selected by a recorded
            # autotune winner (or forced) BEFORE the pallas/dense
            # chain; exact per-step death, one fallback on failure
            from jepsen_tpu.checkers import reach_word
            try:
                with obs.span("reach.walk", engine="reach-word",
                              returns=int(rs.n_returns)):
                    dead, _ = reach_word.walk_returns_words(
                        memo.table, rs.ret_slot[:rs.n_returns],
                        rs.slot_ops[:rs.n_returns], M)
                elapsed = _time.monotonic() - t0
                if dead < 0:
                    return _result_valid("reach-word", stream, memo,
                                         elapsed)
                out = _result_invalid(
                    "reach-word", stream, memo, packed,
                    int(rs.ret_event[dead]), elapsed)
                _attach_witness(out, memo, rs, _build_P(memo, S_pad),
                                S_pad, M, W, int(dead), packed)
                return out
            except Exception as e:                      # noqa: BLE001
                # exactly one record; the pallas/dense chain below is
                # the recorded fallback body
                obs.engine_fallback("word-walk", type(e).__name__,
                                    returns=int(rs.n_returns))
        P_np = _build_P(memo, S_pad)
        if (_use_pallas() and _pallas_fits(S_pad, M, memo.n_ops)
                and should_abort is None):
            # chunk-lockstep first: the batch kernel's per-return
            # amortization applied to this one history (phases chain
            # as async dispatches; ONE round trip on the happy path).
            # Any failure falls through to the sequential lane walk.
            from jepsen_tpu.checkers import reach_chunklock as rcl
            if rcl.enabled() and rcl.admits(S_pad, M, W, rs.n_returns):
                try:
                    with obs.span("reach.walk", engine="reach-chunklock",
                                  returns=int(rs.n_returns)):
                        dead, diag = rcl.walk_chunklock(
                            P_np, rs.ret_slot, rs.slot_ops, M)
                    elapsed = _time.monotonic() - t0
                    if dead < 0:
                        out = _result_valid("reach-chunklock", stream,
                                            memo, elapsed)
                        out.update(diag)
                        return out
                    out = _result_invalid(
                        "reach-chunklock", stream, memo, packed,
                        int(rs.ret_event[dead]), elapsed)
                    out.update(diag)
                    _attach_witness(out, memo, rs, P_np, S_pad, M,
                                    W, int(dead), packed)
                    return out
                except Exception as e:                  # noqa: BLE001
                    _warn_pallas_failed(f"chunklock: {e!r}")
        if (_use_pallas() and _pallas_fits(S_pad, M, memo.n_ops)
                and rs.n_returns >= _PALLAS_MIN_RETURNS):
            R0_np = np.zeros((S_pad, M), bool)
            R0_np[0, 0] = True
            dead = None
            from jepsen_tpu.checkers import reach_lane
            try:
                # third-generation kernel: exact gate-ladder walk (for
                # W > 5, a sound 5-pass-capped walk with an exact
                # rescue on death)
                with obs.span("reach.walk", engine="reach-pallas",
                              returns=int(rs.n_returns)):
                    dead, _ = reach_lane.walk_returns(
                        P_np, rs.ret_slot, rs.slot_ops, R0_np,
                        fetch_R=False, should_abort=should_abort)
            # jtlint: ok fallback — abort verdict returned to the caller, cause inside
            except reach_lane.Aborted:
                return dict(_ABORTED)
            except Exception as e:                      # noqa: BLE001
                _warn_pallas_failed(repr(e))
                try:
                    from jepsen_tpu.checkers import reach_pallas
                    dead, _ = reach_pallas.walk_returns(
                        P_np, rs.ret_slot, rs.slot_ops, R0_np,
                        fetch_R=False)
                except Exception as e2:                 # noqa: BLE001
                    # Mosaic lowering / VMEM allocation failure — the
                    # XLA walk below handles every history the fast
                    # path admits
                    _warn_pallas_failed(repr(e2))
                    dead = None
            if dead is not None:
                elapsed = _time.monotonic() - t0
                if dead < 0:
                    return _result_valid("reach-pallas", stream, memo,
                                         elapsed)
                out = _result_invalid("reach-pallas", stream, memo, packed,
                                      int(rs.ret_event[dead]), elapsed)
                _attach_witness(out, memo, rs, P_np, S_pad, M, W,
                                int(dead), packed)
                return out
        rs = ev.pad_returns(rs, max(64, _bucket(rs.n_returns, _UNROLL)))
        P = jnp.asarray(P_np)
        xc, bm = _xor_bitmask(W, M)
        xc, bm = jnp.asarray(xc), jnp.asarray(bm)
        R0 = jnp.zeros((S_pad, M), jnp.bool_).at[0, 0].set(True)
        if should_abort is not None and rs.R > _ABORT_SEG:
            # abortable serial drive: bounded segments with the config
            # set carried across dispatches, hook checked between
            base, R_cur = 0, R0
            ptr = alive = R_block = None
            while base < rs.R:
                if should_abort():
                    return dict(_ABORTED)
                seg = min(_ABORT_SEG, rs.R - base)
                ptr, R_cur, alive, R_block = _jitted_walk_returns()(
                    P, xc, bm, jnp.asarray(rs.ret_slot[base:base + seg]),
                    jnp.asarray(rs.slot_ops[base:base + seg]), R_cur)
                if not bool(alive):
                    ptr = jnp.int32(base + int(ptr))
                    break
                base += seg
        else:
            with obs.span("reach.walk", engine="reach",
                          returns=int(rs.n_returns)):
                ptr, _, alive, R_block = _jitted_walk_returns()(
                    P, xc, bm, jnp.asarray(rs.ret_slot),
                    jnp.asarray(rs.slot_ops), R0)
        elapsed = _time.monotonic() - t0
        if bool(alive):
            return _result_valid("reach", stream, memo, elapsed)
        dead_event = _refine_dead(P, xc, bm, rs, int(ptr), R_block)
        out = _result_invalid("reach", stream, memo, packed, dead_event,
                              elapsed)
        dead_ret = int(np.searchsorted(rs.ret_event[:rs.n_returns],
                                       dead_event))
        _attach_witness(out, memo, rs, P_np, S_pad, M, W, dead_ret,
                        packed)
        return out
    R0 = jnp.zeros((S_pad, M), jnp.bool_).at[0, 0].set(True)
    slot_op0 = jnp.full((W,), -1, jnp.int32)
    with obs.span("reach.walk", engine="reach-events",
                  events=int(stream.n_events)):
        ptr, _, alive = _jitted_walk()(
            jnp.asarray(T), jnp.asarray(stream.kind),
            jnp.asarray(stream.slot), jnp.asarray(stream.opid), R0,
            slot_op0)
    elapsed = _time.monotonic() - t0
    if bool(alive):
        return _result_valid("reach", stream, memo, elapsed)
    out = _result_invalid("reach", stream, memo, packed,
                          int(ptr) - 1, elapsed)
    _attach_witness_slow(out, memo, stream, T, S_pad, M, W,
                         int(ptr) - 1, packed)
    return out


def _union_alphabet(model: Model, packed_list, live, max_states: int):
    """One memo over the UNION of the keys' op alphabets, plus a per-key
    LUT from local op ids to union ids (last entry maps -1 → -1, so free
    slots survive fancy-indexing). Per-key tables are history-dependent
    (ids assigned by occurrence order), so even identical workloads get
    different tables; the union table is what lets every key share one
    device-resident P."""
    union: Dict[Any, int] = {}          # (f, hashable(value)) -> union id
    union_ops: List[Op] = []
    for i in live:
        p = packed_list[i]
        for key, op in zip(h.op_keys_of(p), p.distinct_ops):
            if key not in union:
                union[key] = len(union_ops)
                union_ops.append(op)
    memo_u = _memo_for_ops(model, tuple(union_ops),
                           max_states=max_states)
    luts = {}
    for i in live:
        keys_i = h.op_keys_of(packed_list[i])
        lut = np.fromiter((union[k] for k in keys_i),
                          np.int32, count=len(keys_i))
        luts[i] = np.append(lut, np.int32(-1))
    return memo_u, luts


def _keyed_operands(model, packed_list, rss, live, W: int,
                    max_states: int):
    """Build the keyed kernel's flat operands: union transition tensor P
    plus all keys' REAL returns concatenated into one stream tagged with
    key ids. Returns ``(P, ret_flat, ops_flat, key_flat, offsets, wide)``;
    raises :class:`StateExplosion`/:class:`DenseOverflow` when the union
    alphabet does not fit the kernel's budgets. Shared between
    :func:`_check_many_keyed` and its differential tests so both exercise
    the same flattening."""
    memo_u, luts = _union_alphabet(model, packed_list, live, max_states)
    S_pad = max(2, _next_pow2(memo_u.n_states))
    M = 1 << W
    if not (_fast_ok(S_pad, W, M, memo_u.n_ops)
            and _pallas_fits(S_pad, M, memo_u.n_ops)):
        raise DenseOverflow("union alphabet exceeds keyed-kernel budgets")
    P = _build_P(memo_u, S_pad)
    wide = [ev.pad_returns(r, r.n_returns, W) for r in rss]
    counts = [r.n_returns for r in wide]
    offsets = np.concatenate([[0], np.cumsum(counts)])
    ret_flat = np.concatenate(
        [r.ret_slot[:n] for r, n in zip(wide, counts)] or
        [np.zeros(0, np.int32)])
    ops_flat = np.concatenate(
        [luts[i][r.slot_ops[:n]] for i, r, n in
         zip(live, wide, counts)] or
        [np.zeros((0, W), np.int32)])
    key_flat = np.repeat(np.arange(len(wide), dtype=np.int32), counts)
    return P, ret_flat, ops_flat, key_flat, offsets, wide


def _check_many_keyed(model, rss, preps, live, results, packed_list,
                      M: int, W: int, max_states: int, t0: float
                      ) -> Optional[List[Dict[str, Any]]]:
    """Per-key batch on the keyed pallas kernel: all keys' REAL returns
    concatenated into one flat stream (zero padding waste), one kernel
    launch, exact per-key death indices. Ops are remapped into the union
    alphabet so every key shares one transition tensor. Returns the
    filled result list, or None to fall through to the vmapped XLA path
    (union too large, or kernel failure)."""
    from jepsen_tpu.checkers import reach_pallas

    try:
        P, ret_flat, ops_flat, key_flat, offsets, wide = _keyed_operands(
            model, packed_list, rss, live, W, max_states)
    # jtlint: ok fallback — batch-capability probe: None routes to per-key, which records
    except (StateExplosion, DenseOverflow):
        return None
    try:
        # second-generation keyed kernel (unconditional exact passes,
        # pipelined gather); first-generation kernel as fallback
        from jepsen_tpu.checkers import reach_lane
        dead = reach_lane.walk_returns_keyed(
            P, ret_flat, ops_flat, key_flat, len(wide), M)
    except Exception as e:                              # noqa: BLE001
        _warn_pallas_failed(repr(e))
        try:
            dead = reach_pallas.walk_returns_keyed(
                P, ret_flat, ops_flat, key_flat, len(wide), M)
        except Exception as e2:                         # noqa: BLE001
            _warn_pallas_failed(repr(e2))
            return None
    elapsed = _time.monotonic() - t0
    for k, i in enumerate(live):
        memo, stream = preps[i][0], preps[i][1]
        if int(dead[k]) < 0:
            results[i] = _result_valid("reach-keyed", stream, memo,
                                       elapsed)
        else:
            local = int(dead[k]) - int(offsets[k])
            results[i] = _result_invalid(
                "reach-keyed", stream, memo, packed_list[i],
                int(wide[k].ret_event[local]), elapsed)
            # witness decode runs in the key's LOCAL alphabet/geometry
            # (wide[k] carries union op ids the per-key memo can't name)
            rs_k = ev.returns_view(stream)
            W_k = max(stream.W, 1)
            _attach_witness(results[i], memo, rs_k,
                            _build_P(memo, preps[i][3]), preps[i][3],
                            1 << W_k, W_k, local, packed_list[i])
    return results


class _UnionPrepA:
    """Stage A of the split union prep (ISSUE 3 tentpole): everything
    that must be built ONCE per batch — the union alphabet, its memo
    and noop classification — plus each live key's packed arrays with
    op ids remapped into the union alphabet, the inputs stage B's
    per-group native packing (:func:`_union_pack_group`) consumes.
    Pure host data; safe to share with the streaming prep thread."""
    __slots__ = ("memo_u", "S_pad", "noop_op", "opids", "invs", "rets",
                 "crs", "_P", "_cats", "pack_s")

    def __init__(self, memo_u, S_pad, noop_op, opids, invs, rets, crs):
        self.memo_u = memo_u
        self.S_pad = S_pad
        self.noop_op = noop_op
        self.opids = opids
        self.invs = invs
        self.rets = rets
        self.crs = crs
        self._P = None
        self._cats = None
        # cumulative stage-B wall (native packing) over this batch —
        # the synchronous scheduler's prep.wall_s base, so stream and
        # sync report the SAME quantity (packing + marshalling)
        self.pack_s = 0.0

    def P(self) -> np.ndarray:
        """Union transition tensor, built once on first use (streaming
        and synchronous consumers share it)."""
        if self._P is None:
            self._P = _build_P(self.memo_u, self.S_pad)
        return self._P

    def cats(self):
        """Full-batch concatenations ``(inv, ret, opid, crs, offs)``,
        built once — the synchronous whole-batch stage B and the
        result-assembly accounting both need them, and re-concatenating
        per consumer was a multi-hundred-MB memcpy at 4096×100k."""
        if self._cats is None:
            offs = np.zeros(len(self.opids) + 1, np.int64)
            for j in range(len(self.opids)):
                offs[j + 1] = offs[j] + len(self.opids[j])
            self._cats = (np.concatenate(self.invs),
                          np.concatenate(self.rets),
                          np.concatenate(self.opids),
                          np.concatenate(self.crs), offs)
        return self._cats

    def drop_per_key(self) -> np.ndarray:
        """Per-live-key count of dropped crashed-noop entries (the
        events accounting of :func:`_union_results`)."""
        return np.array(
            [int((self.crs[j] & self.noop_op[self.opids[j]]).sum())
             for j in range(len(self.opids))], np.int64)


def _union_stage_a(model: Model,
                   packed_list: Sequence[h.PackedHistory],
                   live: Sequence[int],
                   max_states: int) -> Optional["_UnionPrepA"]:
    """Build stage A, or None when the union alphabet explodes or ops
    are unhashable (callers fall back to per-history paths)."""
    union: Dict[Any, int] = {}
    union_ops: List[Op] = []
    try:
        for i in live:
            p = packed_list[i]
            for key, op in zip(h.op_keys_of(p), p.distinct_ops):
                if key not in union:
                    union[key] = len(union_ops)
                    union_ops.append(op)
        memo_u = _memo_for_ops(model, tuple(union_ops),
                               max_states=max_states)
    # jtlint: ok fallback — batch-capability probe: None routes to per-key, which records
    except (StateExplosion, TypeError):
        return None
    S_pad = max(2, _next_pow2(memo_u.n_states))
    tbl = memo_u.table
    states = np.arange(tbl.shape[0], dtype=tbl.dtype)[:, None]
    noop_op = np.all((tbl == states) | (tbl == -1), axis=0)
    opids, invs, rets, crs = [], [], [], []
    for i in live:
        p = packed_list[i]
        keys = h.op_keys_of(p)
        lut = np.fromiter((union[k] for k in keys), np.int32,
                          count=len(keys))
        opids.append(lut[p.op_id])
        invs.append(p.inv_ev)
        rets.append(p.ret_ev)
        crs.append(p.crashed)
    return _UnionPrepA(memo_u, S_pad, noop_op, opids, invs, rets, crs)


def _union_pack_group(sa: "_UnionPrepA", sel: Sequence[int],
                      max_slots: int):
    """Stage B: native packing (``preproc_native.build_keyed``) of the
    keys at positions ``sel`` of the live axis — per dispatch group in
    the streaming pipeline, or all live keys at once on the
    synchronous path. Returns ``(ret_flat, ops_flat, key_W, key_R,
    offsets, W)`` or None (native lib missing, or slot overflow under
    the union memo's coarser noop classification — union-noop ⊆
    per-key-noop, so a key near the max_slots boundary can overflow
    here yet fit the general per-key path; genuine overflow raises
    ConcurrencyOverflow from the per-key build later). Host-only work
    (numpy + the GIL-releasing native lib): safe on the prep thread."""
    from jepsen_tpu.checkers import preproc_native

    t0 = _time.monotonic()
    sel = list(sel)
    if sel == list(range(len(sa.opids))):
        # whole-batch selection (the synchronous path): reuse stage
        # A's cached concatenations instead of re-building them
        inv_c, ret_c, opid_c, crs_c, offs = sa.cats()
    else:
        offs = np.zeros(len(sel) + 1, np.int64)
        for j, k in enumerate(sel):
            offs[j + 1] = offs[j] + len(sa.opids[k])
        inv_c = np.concatenate([sa.invs[k] for k in sel])
        ret_c = np.concatenate([sa.rets[k] for k in sel])
        opid_c = np.concatenate([sa.opids[k] for k in sel])
        crs_c = np.concatenate([sa.crs[k] for k in sel])
    built = preproc_native.build_keyed(
        offs, inv_c, ret_c, opid_c, crs_c,
        sa.noop_op, max_slots, max_slots)
    sa.pack_s += _time.monotonic() - t0
    if built is None:
        return None
    ret_flat, ops_wide, _pend, key_W, key_R, _ret_entry, _R_tot = built
    if (key_W < 0).any():
        return None
    W = max(int(key_W.max()), 1)
    ops_flat = np.ascontiguousarray(ops_wide[:, :W])
    offsets = np.concatenate([[0], np.cumsum(key_R)])
    return ret_flat, ops_flat, key_W, key_R, offsets, W


def _union_prep(model: Model, packed_list: Sequence[h.PackedHistory],
                live: Sequence[int], max_states: int, max_slots: int,
                need_pallas: bool = True,
                stage_a: Optional["_UnionPrepA"] = None):
    """Shared union-alphabet native preprocessing for the batched
    device engines (keyed kernel and the lockstep batch kernel): ONE
    memo over the union of every history's op alphabet + ONE native
    call building every history's slotted return stream — composed
    from the stage A / stage B split the streaming pipeline reuses
    per-group (a prebuilt ``stage_a`` skips the union BFS, so a
    streaming→synchronous fallback never pays it twice). Returns None
    when the union explodes, ops are unhashable, the native lib is
    missing, the kernels' dense budgets don't fit, or a history
    overflows max_slots under the union memo's coarser noop
    classification (callers fall back to per-history paths, whose
    per-key noop dropping may still fit — and which raise
    ConcurrencyOverflow on genuine overflow). ``need_pallas=False``
    skips the Pallas VMEM gate for consumers that only run the XLA
    walk (the mesh lane)."""
    sa = stage_a if stage_a is not None else _union_stage_a(
        model, packed_list, live, max_states)
    if sa is None:
        return None
    g = _union_pack_group(sa, range(len(live)), max_slots)
    if g is None:
        return None
    ret_flat, ops_flat, key_W, key_R, offsets, W = g
    M = 1 << W
    memo_u, S_pad, noop_op = sa.memo_u, sa.S_pad, sa.noop_op
    if not (_fast_ok(S_pad, W, M, memo_u.n_ops)
            and (not need_pallas
                 or _pallas_fits(S_pad, M, memo_u.n_ops))):
        return None                     # general path may still fit
    _inv_c, _ret_c, opid_cat, crs_cat, offs = sa.cats()
    P = sa.P()
    return (memo_u, S_pad, P, W, M, ret_flat, ops_flat, key_W, key_R,
            offsets, opid_cat, crs_cat, offs, noop_op)


# histories per lockstep dispatch. Two measured hardware ceilings
# bound the width (both from compile failures at the headline
# geometry, W=5 S=8): SMEM holds 1 MB — the B*H*W i32 double-buffered
# slot_ops window is kept under it by shrinking the block size as H
# grows (reach_batch._adaptive_block: B=1024 to H=16, 512 at H=32) —
# and VMEM holds 16 MB scoped, which the H=64 f32 geometry exceeded
# by 212 KB (the 2×[HS, W·HS] transition scratch is 10.5 MB alone in
# f32; the bf16 compute dtype halves it, so H=64 now COMPILES — but
# loses per-history to H=32 on step cost, so it stays non-default).
# H=32 is the e2e winner (one dispatch group + one fetch over 32
# histories: 3.2M agg ops/s vs 2.3M at H=16 on 32×cas-100k) while
# per-history-return kernel cost is ~flat from H=16 (43-60 ns across
# sessions). Wider batches chunk into groups.
_BATCH_GROUP = 32


def check_batch(model: Model, packed_list: Sequence[h.PackedHistory], *,
                max_states: int = 100_000, max_slots: int = 20,
                max_dense: int = 1 << 22,
                devices: Optional[Sequence] = None,
                group: int = _BATCH_GROUP,
                diag: Optional[dict] = None) -> List[Dict[str, Any]]:
    """Check SEVERAL complete histories at once on the lockstep batch
    kernel (:mod:`jepsen_tpu.checkers.reach_batch`): the config sets of
    up to ``group`` histories advance together, one return index per
    step, so the per-issue latency wall of the sequential walk is paid
    once per step instead of once per history — measured ~3.5-4x the
    C++ WGL engine's aggregate throughput on 8 x cas-100k (one chip vs
    one core; BASELINE.md round-4 batch rung).

    The natural fit is a Jepsen run that produced multiple large
    histories (``test-count > 1``, per-node sub-histories, or repeated
    soak iterations). Falls back to sequential :func:`check_packed`
    per history whenever the lockstep gates don't hold (non-uniform
    workloads whose union memo explodes, Pallas unavailable, > max
    slots, tiny histories). Verdicts and witnesses are identical to
    the sequential path (differentially tested). Upstream analogue:
    none — knossos checks one history per run (SURVEY.md §2.2).

    With ``devices`` (>1) the HISTORY axis shards over a
    ``jax.sharding.Mesh`` instead: whole histories are as independent
    as ``independent`` keys, so the batch rides the same mesh routes
    as :func:`check_many` — the MESH-LOCKSTEP lane first (lockstep
    lane blocks placed per device, groups multi-queued so chips walk
    concurrently), then the keyed mesh-union walk. The
    graceful-fallback guarantee survives the mesh: a mesh-lockstep
    dispatch failure degrades to the single-device lockstep scheduler
    (exactly one ``mesh-lockstep`` obs fallback — never silently the
    keyed kernel), and if the sharded batch cannot run at all (e.g.
    padding every history to the common shape overflows ``max_dense``
    even though each fits alone), the call falls through to the
    single-device route below and its per-history fallbacks, rather
    than raising where ``devices=None`` would have succeeded."""
    _ensure_persistent_caches()
    if devices is not None and len(devices) > 1:
        try:
            # group and diag ride along: the sharded path's dispatch
            # width and mesh diagnostics must not vanish just because
            # a mesh was supplied
            return check_many(model, packed_list, max_states=max_states,
                              max_slots=max_slots, max_dense=max_dense,
                              devices=devices, group=group, diag=diag)
        except (DenseOverflow, ev.ConcurrencyOverflow,
                StateExplosion) as e:
            logging.getLogger("jepsen.reach").warning(
                "sharded history batch failed (%r); falling back to "
                "the single-device path", e)
            obs.engine_fallback("reach-batch-mesh", type(e).__name__,
                                histories=len(packed_list))
        except Exception as e:                          # noqa: BLE001
            # jax/XLA runtime failures (mesh shape, compile, OOM) keep
            # the graceful fallback; genuine programming errors
            # (NameError, shape bugs in our code) must surface, not
            # silently degrade every sharded batch
            if not _raised_from_jax(e):
                raise
            # full traceback at warning level: a silent degrade must
            # leave enough evidence to distinguish "OOM on this mesh"
            # from a misclassified programming error
            logging.getLogger("jepsen.reach").warning(
                "sharded history batch failed (%r); falling back to "
                "the single-device path", e, exc_info=e)
            obs.engine_fallback("reach-batch-mesh", type(e).__name__,
                                histories=len(packed_list), jax=True)
    t0 = _time.monotonic()
    results: List[Optional[Dict[str, Any]]] = [
        {"valid": True, "engine": "reach-lockstep", "events": 0,
         "time-s": 0.0} if (p.n == 0 or p.n_ok == 0) else None
        for p in packed_list]
    live = [i for i, r in enumerate(results) if r is None]
    if not live:
        return results  # type: ignore[return-value]
    u = None
    sa = None
    from jepsen_tpu.checkers import preproc_native
    if _use_pallas() and preproc_native.available() and len(live) >= 2:
        sa = _union_stage_a(model, packed_list, live, max_states)
        if sa is not None:
            if _stream_prep_enabled():
                # tentpole path: per-group packing streams from a prep
                # thread while earlier groups walk on device
                out = _check_lockstep_stream(
                    "reach-lockstep", model, packed_list, live, sa,
                    max_states, max_slots, max_dense,
                    group or _BATCH_GROUP, diag, t0)
                if out is not None:
                    return out
            u = _union_prep(model, packed_list, live, max_states,
                            max_slots, stage_a=sa)
    if u is None:
        # the ISSUE-named silent degradation point: the lockstep batch
        # quietly became H sequential per-history checks
        obs.engine_fallback("reach-lockstep", "no-union-prep",
                            histories=len(live))
        for i in live:
            results[i] = check_packed(model, packed_list[i],
                                      max_states=max_states,
                                      max_slots=max_slots,
                                      max_dense=max_dense)
        return results  # type: ignore[return-value]
    (memo_u, S_pad, P, W, M, ret_flat, ops_flat, key_W, key_R,
     offsets, opid_cat, crs_cat, offs, noop_op) = u
    from jepsen_tpu.checkers import reach_batch
    try:
        # length-bucketed lane packing + pipelined group dispatch: a
        # ragged batch no longer pads every history to the longest,
        # and group g+1's marshalling/compile hides under group g's
        # device walk
        groups = reach_batch.plan_buckets(
            [int(r) for r in key_R], W, group=group)
        dead = _dispatch_lockstep_groups(
            P, ret_flat, ops_flat, offsets, groups, M, len(live), diag,
            prep_base_s=sa.pack_s if sa is not None else 0.0)
    except Exception as e:                              # noqa: BLE001
        _warn_pallas_failed(repr(e))
        obs.engine_fallback("reach-lockstep", type(e).__name__,
                            histories=len(live))
        for i in live:
            results[i] = check_packed(model, packed_list[i],
                                      max_states=max_states,
                                      max_slots=max_slots,
                                      max_dense=max_dense)
        return results  # type: ignore[return-value]
    elapsed = _time.monotonic() - t0
    return _union_results("reach-lockstep", model, packed_list, live,
                          dead, u, elapsed, max_states, max_slots,
                          max_dense)


def _union_stage_a_shared(model: Model, packed_list, live,
                          max_states: int, u_box: Optional[dict]
                          ) -> Optional["_UnionPrepA"]:
    """One :func:`_union_stage_a` per ``check_many`` call, shared by
    the streaming pipeline, the synchronous lockstep lane, and the
    keyed lane (the union BFS is the expensive half of the old
    monolithic prep — a streaming→synchronous fallback must not pay
    it twice). Caches the result — including a failed (None) one."""
    if u_box is not None and "sa" in u_box:
        return u_box["sa"]
    sa = _union_stage_a(model, packed_list, live, max_states)
    if u_box is not None:
        u_box["sa"] = sa
    return sa


def _union_prep_shared(model: Model, packed_list, live,
                       max_states: int, max_slots: int,
                       u_box: Optional[dict]):
    """One :func:`_union_prep` per ``check_many`` call: the lockstep
    and keyed lanes take identical ``(live, max_states, max_slots,
    need_pallas=True)`` preps, so when the first lane declines (or its
    kernel fails) the second must not pay the union-alphabet BFS +
    native build again (~2 s of host time at 4096 keys). ``u_box``
    caches the result — including a failed (None) prep — and reuses a
    cached stage A from the streaming attempt."""
    if u_box is not None and "u" in u_box:
        return u_box["u"]
    sa = _union_stage_a_shared(model, packed_list, live, max_states,
                               u_box)
    u = None if sa is None else _union_prep(
        model, packed_list, live, max_states, max_slots, stage_a=sa)
    if u_box is not None:
        u_box["u"] = u
    return u


def _check_many_native(model: Model,
                       packed_list: Sequence[h.PackedHistory],
                       max_states: int, max_slots: int, max_dense: int,
                       t0: float, u_box: Optional[dict] = None
                       ) -> Optional[List[Dict[str, Any]]]:
    """Uniform-workload fast lane for :func:`check_many`: ONE union
    memo + ONE batched native preprocessing call
    (``preproc_native.build_keyed``) replace the per-key
    memo-signature/BFS-projection/event-build/ctypes pipeline that cost
    ~2 s of host time at 4096 keys. The union alphabet serves every key
    (per-key memos are only needed for failure witnesses, decoded
    lazily per failed key). Returns the results list, or None to fall
    through to the general path (native lib unavailable, union
    explosion, kernel budgets exceeded, slot overflow under the union
    memo's coarser noop classification, or too few returns to beat the
    XLA batch); genuine > max_slots concurrency then raises
    :class:`~jepsen_tpu.checkers.events.ConcurrencyOverflow` from the
    per-key build."""
    from jepsen_tpu.checkers import preproc_native, reach_pallas

    if not (_use_pallas() and preproc_native.available()):
        return None
    live = [i for i, p in enumerate(packed_list) if p.n and p.n_ok]
    total_returns = sum(packed_list[i].n_ok for i in live)
    if not live or total_returns < _PALLAS_MIN_RETURNS:
        return None
    u = _union_prep_shared(model, packed_list, live, max_states,
                           max_slots, u_box)
    if u is None:
        return None
    (memo_u, S_pad, P, W, M, ret_flat, ops_flat, key_W, key_R,
     offsets, opid_cat, crs_cat, offs, noop_op) = u
    key_flat = np.repeat(np.arange(len(live), dtype=np.int32), key_R)
    try:
        from jepsen_tpu.checkers import reach_lane
        dead = reach_lane.walk_returns_keyed(
            P, ret_flat, ops_flat, key_flat, len(live), M)
    except Exception as e:                              # noqa: BLE001
        _warn_pallas_failed(repr(e))
        try:
            dead = reach_pallas.walk_returns_keyed(
                P, ret_flat, ops_flat, key_flat, len(live), M)
        except Exception as e2:                         # noqa: BLE001
            _warn_pallas_failed(repr(e2))
            return None
    elapsed = _time.monotonic() - t0
    # flat dead indices (into the concatenated keyed stream) -> local
    # per-key return indices; the shared union assembly decodes the
    # rare failed key in its own geometry (same return ordering —
    # drops only remove crashed entries, which never return)
    dead_local = np.array(
        [int(d) - int(offsets[k]) if int(d) >= 0 else -1
         for k, d in enumerate(dead)], np.int64)
    return _union_results("reach-keyed", model, packed_list, live,
                          dead_local, u, elapsed, max_states,
                          max_slots, max_dense)


def _union_valid_result(engine: str, p: h.PackedHistory, dropped: int,
                        key_R_k: int, key_W_k: int, n_states: int,
                        elapsed: float) -> Dict[str, Any]:
    """Valid verdict from the union geometry — shared by the keyed,
    lockstep, and mesh union lanes (one source for the events/slots
    accounting)."""
    return {"valid": True, "engine": engine,
            "events": (p.n - dropped) + key_R_k,
            "slots": key_W_k, "states": n_states,
            "dropped-crashed-noops": dropped, "time-s": elapsed}


def _union_results(engine: str, model: Model,
                   packed_list: Sequence[h.PackedHistory],
                   live: Sequence[int], dead_local: np.ndarray, u,
                   elapsed: float, max_states: int, max_slots: int,
                   max_dense: int) -> List[Dict[str, Any]]:
    """Assemble per-history results from a full :func:`_union_prep`
    tuple — thin adapter over :func:`_union_results_parts` for the
    keyed/lockstep/mesh lanes that carry one."""
    (memo_u, _S_pad, _P, _W, _M, _ret_flat, _ops_flat, key_W, key_R,
     _offsets, opid_cat, crs_cat, offs, noop_op) = u
    drop_cat = (crs_cat & noop_op[opid_cat]).astype(np.int64)
    drop_per_key = np.add.reduceat(drop_cat, offs[:-1])
    return _union_results_parts(engine, model, packed_list, live,
                                dead_local, memo_u, key_W, key_R,
                                drop_per_key, elapsed, max_states,
                                max_slots, max_dense)


def _union_results_parts(engine: str, model: Model,
                         packed_list: Sequence[h.PackedHistory],
                         live: Sequence[int], dead_local: np.ndarray,
                         memo_u: Memo, key_W, key_R,
                         drop_per_key: np.ndarray, elapsed: float,
                         max_states: int, max_slots: int,
                         max_dense: int) -> List[Dict[str, Any]]:
    """Assemble per-history results from union-geometry verdicts —
    shared by the keyed and lockstep lanes of :func:`check_many`, by
    :func:`check_batch`, and by the streaming pipeline (which carries
    per-group ``key_W``/``key_R`` instead of a prep tuple).
    ``dead_local[k]`` is live history k's LOCAL dead return index
    (-1 = linearizable). Valid histories are answered from the union
    accounting; the rare failed history decodes in its OWN geometry
    with the full witness pipeline."""
    results: List[Optional[Dict[str, Any]]] = [
        {"valid": True, "engine": engine, "events": 0,
         "time-s": 0.0} if (packed_list[i].n == 0
                            or packed_list[i].n_ok == 0) else None
        for i in range(len(packed_list))]
    for k, i in enumerate(live):
        p = packed_list[i]
        dropped = int(drop_per_key[k])
        if int(dead_local[k]) < 0:
            results[i] = _union_valid_result(
                engine, p, dropped, int(key_R[k]), int(key_W[k]),
                memo_u.n_states, elapsed)
        else:
            local = int(dead_local[k])
            memo_k, stream_k, _Tk, S_k, M_k = _prep(
                model, p, max_states=max_states, max_slots=max_slots,
                max_dense=max_dense)
            rs_k = ev.returns_view(stream_k)
            W_k = max(stream_k.W, 1)
            results[i] = _result_invalid(
                engine, stream_k, memo_k, p,
                int(rs_k.ret_event[local]), elapsed)
            _attach_witness(results[i], memo_k, rs_k,
                            _build_P(memo_k, S_k), S_k, M_k, W_k,
                            local, p)
    return results  # type: ignore[return-value]


# in-flight lockstep dispatch groups beyond the one being collected —
# see dispatch_core.PIPE_DEPTH (the extracted dispatch/collect core
# both lockstep engines share).
_LOCKSTEP_PIPE_DEPTH = dispatch_core.PIPE_DEPTH


def _lockstep_accounting(gdiags: List[dict], prep_s: float,
                         hidden_s: float, stall_s: float,
                         dispatch_s: float, fetch_s: float, mode: str,
                         queue_hwm: int,
                         diag: Optional[dict],
                         mesh: Optional[dict] = None,
                         fetch_degraded: bool = False) -> None:
    """Shared obs/diag accounting tail of the synchronous and streaming
    lockstep schedulers: pack efficiency, kernel-cache counters, and
    the prep/dispatch/fetch wall breakdown. ``prep.hidden_s`` is the
    prep wall time that did NOT extend the critical path (prep minus
    the consumer's queue stalls) — the overlap win as ONE tracked
    number; on the synchronous path it is 0 by construction. ``mesh``
    (device-sharded dispatches only) carries the device count,
    per-device dispatched-group counts, and the in-flight high-water
    mark — the stream-overlap evidence of the multi-queue scheduler —
    emitted as ``lockstep.mesh.*`` and mirrored into ``diag``."""
    from jepsen_tpu.checkers import reach_batch
    from jepsen_tpu.checkers import transfer as _xfer

    # replicated pad lanes (mesh group splitting) are walked but not
    # real work: their returns are excluded so real_returns and
    # pack_efficiency don't overstate mesh packing quality
    real = sum(d["real_returns"] - d.get("pad_lane_returns", 0)
               for d in gdiags)
    padded = sum(d["padded_returns"] for d in gdiags)
    cache = reach_batch.kernel_cache_info()
    # bucket pack efficiency and kernel-cache counters flow to obs on
    # EVERY dispatch (cache counters are cumulative, so gauges), not
    # only when a caller passes a diag dict
    obs.count("lockstep.groups", len(gdiags))
    obs.count("lockstep.real_returns", real)
    obs.count("lockstep.padded_returns", padded)
    obs.gauge("lockstep.pack_efficiency", round(real / max(padded, 1), 4))
    obs.gauge("lockstep.kernel_cache.hits", cache["hits"])
    obs.gauge("lockstep.kernel_cache.misses", cache["misses"])
    obs.gauge("lockstep.kernel_cache.entries", cache["entries"])
    obs.gauge("prep.wall_s", round(prep_s, 6))
    obs.gauge("prep.hidden_s", round(hidden_s, 6))
    obs.gauge("prep.stall_s", round(stall_s, 6))
    obs.gauge("prep.queue_depth_max", queue_hwm)
    obs.gauge("prep.mode", mode)
    # transfer-diet evidence per dispatch: actual wire bytes vs the
    # blanket int32/f32 format, and which fetch protocol answered
    put_b = sum(d.get("put_bytes", 0) for d in gdiags)
    put_u = sum(d.get("put_bytes_unpacked", 0) for d in gdiags)
    # the PROTOCOL THE VERDICTS ACTUALLY CROSSED ON, not the env gate:
    # a lazy-fetch fallback mid-run degraded at least one collect to
    # eager full-array fetches
    fmode = "degraded-eager" if fetch_degraded else _xfer.fetch_mode()
    obs.gauge("transfer.fetch_mode", fmode)
    if mesh is not None:
        obs.gauge("lockstep.mesh.devices", mesh["n_devices"])
        obs.gauge("lockstep.mesh.inflight_max", mesh["inflight_max"])
        if mesh.get("pad_lanes"):
            # counted HERE — once per completed dispatch — so a
            # stream→sync retry of the same batch can't double-count
            obs.count("lockstep.mesh.pad_lanes", mesh["pad_lanes"])
        for k, c in enumerate(mesh["per_device_groups"]):
            if c:
                obs.count(f"lockstep.mesh.groups.dev{k}", c)
    if diag is not None:
        diag["groups"] = gdiags
        diag["real_returns"] = real
        diag["padded_returns"] = padded
        diag["pack_efficiency"] = round(real / max(padded, 1), 4)
        diag["kernel_cache"] = cache
        diag["dispatch_s"] = round(dispatch_s, 6)
        diag["fetch_s"] = round(fetch_s, 6)
        diag["prep"] = {"mode": mode, "wall_s": round(prep_s, 6),
                        "hidden_s": round(hidden_s, 6),
                        "stall_s": round(stall_s, 6),
                        "queue_depth_max": queue_hwm,
                        "groups": len(gdiags)}
        diag["transfer"] = {"packed_bytes": put_b,
                            "unpacked_bytes": put_u,
                            "fetch_mode": fmode}
        if mesh is not None:
            diag["mesh"] = dict(mesh)


# the shared dispatch/collect state machine now lives in
# dispatch_core (both lockstep engines and the multi-host chunk path
# parameterize ONE implementation); the alias keeps this module's
# scheduler code and its historical name readable
_LockstepDispatchState = dispatch_core.DispatchState


def _dispatch_lockstep_groups(P, ret_flat, ops_flat, offsets, groups,
                              M: int, n_live: int,
                              diag: Optional[dict] = None,
                              prep_base_s: float = 0.0,
                              devices: Optional[Sequence] = None,
                              pad_lanes: int = 0) -> np.ndarray:
    """Bucketed, pipelined lockstep dispatch (the SYNCHRONOUS
    scheduler — the streaming pipeline's fallback and the verdict
    reference of its differential tests): each group in ``groups``
    (index lists into the live-key axis, from
    :func:`reach_batch.plan_buckets`) walks the batch kernel in its own
    geometry; group g+1's walk is QUEUED before group g's verdicts are
    fetched, so host marshalling/compiles overlap device walks. The
    per-geometry compiled-kernel cache (``reach_batch._batch_call``)
    makes repeated geometries free across groups and calls. With
    ``devices`` the groups (lane blocks, pre-split by
    :func:`reach_batch.shard_groups_for_mesh`) are placed round-robin
    over the mesh and the in-flight window widens to one walking plus
    one queued group PER DEVICE — device k walks group g while device
    j walks group g+1, and FIFO collection drains the oldest shard
    while the rest keep walking. Fills ``diag`` (when given) with
    per-group geometry, pack efficiency (real vs padded returns),
    kernel-cache counters, and the prep/dispatch/fetch wall breakdown.
    Returns the per-live-key local dead indices."""
    from jepsen_tpu.checkers import reach_batch

    dead = np.full(n_live, -1, np.int64)
    st = _LockstepDispatchState(devices, dead)
    # prep_base_s carries the caller's stage-B packing wall
    # (sa.pack_s) so sync prep.wall_s covers packing + marshalling —
    # the same quantity the streaming scheduler reports
    prep_s = prep_base_s
    dispatch_s = 0.0
    gdiags: List[dict] = []
    for gi, g in enumerate(groups):
        t0 = _time.monotonic()
        with obs.span("lockstep.prep", lanes=len(g)):
            prep = reach_batch.prepare_returns_batch(
                P,
                [ret_flat[offsets[k]:offsets[k + 1]] for k in g],
                [ops_flat[offsets[k]:offsets[k + 1]] for k in g],
                M)
        t1 = _time.monotonic()
        prep_s += t1 - t0
        gdiags.append(st.stage(gi, g, prep,
                               reach_batch.dispatch_prepared))
        dispatch_s += _time.monotonic() - t1
        st.collect(st.depth)
    st.collect(0)
    _lockstep_accounting(gdiags, prep_s, 0.0, 0.0, dispatch_s,
                         st.fetch_s, "sync", 0, diag,
                         st.mesh_info(pad_lanes), st.fetch_degraded)
    return dead


# bounded handoff between the streaming prep thread and the dispatch
# loop: depth 2 keeps one marshalled group waiting while another packs,
# without pinning unbounded host operand sets in memory
_PREP_QUEUE_DEPTH = 2


def _stream_prep_enabled() -> bool:
    """The streaming prep→dispatch pipeline is on by default wherever
    the lockstep lane runs; ``JEPSEN_TPU_NO_STREAM_PREP=1`` forces the
    synchronous scheduler (consulted per call — tests toggle it)."""
    return not os.environ.get("JEPSEN_TPU_NO_STREAM_PREP")


def _dispatch_lockstep_stream(sa: "_UnionPrepA", groups,
                              max_slots: int, n_live: int,
                              diag: Optional[dict],
                              devices: Optional[Sequence] = None,
                              pad_lanes: int = 0):
    """Streaming producer/consumer lockstep scheduler (the ISSUE 3
    tentpole): a background prep thread runs per-group native packing
    (:func:`_union_pack_group`) and operand marshalling
    (:func:`reach_batch.prepare_returns_batch`) and feeds this thread
    through a bounded queue — group 0 walks on device while groups
    1..G are still being packed, extending the
    ``dispatch_returns_batch``/``collect_returns_batch`` split
    upstream into host prep. All jax work (device puts, compiles,
    dispatches, fetches) stays on the calling thread; the producer
    touches only numpy and the GIL-releasing native lib, so the two
    genuinely overlap.

    Returns ``(dead, key_W, key_R)`` over the live axis, or None when
    the producer declined (slot overflow / budget gates) or raised —
    the caller falls back to the synchronous path, reusing stage A, so
    verdicts stay bit-identical by construction. Exactly one
    ``stream-prep`` fallback lands in the obs ledger on that path, and
    the queue is drained so the producer can never deadlock on a full
    queue. Overlap efficiency is tracked: ``prep.wall_s`` (total prep
    thread work) vs ``prep.hidden_s`` (prep time that did not extend
    the critical path — wall minus the consumer's queue stalls).

    With ``devices`` the consumer becomes the MULTI-QUEUE dispatcher of
    the mesh lockstep lane: arriving groups (lane blocks) are placed
    round-robin over the mesh with one walking plus one queued group
    per device, so the ONE prep thread feeds N concurrently-walking
    chips — device k walks group g while device j walks group g+1 and
    the producer packs g+2. FIFO collection drains the oldest shard
    while the rest keep walking; fallback guarantees are unchanged
    (the fallback target is the caller's, which for the mesh lane is
    the single-device lockstep scheduler, never the keyed kernel)."""
    import queue as _queue

    from jepsen_tpu.checkers import reach_batch

    P = sa.P()
    q: "_queue.Queue" = _queue.Queue(maxsize=_PREP_QUEUE_DEPTH)
    stop = threading.Event()
    prep_wall = [0.0]
    queue_hwm = [0]

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return True
            # jtlint: ok fallback — bounded producer backoff: retried until should_abort fires
            except _queue.Full:
                continue
        return False

    def _producer() -> None:
        try:
            if os.environ.get("JEPSEN_TPU_SERVE_FAULTS"):
                # self-nemesis hook (jepsen_tpu/serve/faults.py):
                # injected prep-thread death — exercises the
                # exactly-once stream-prep fallback from a REAL chaos
                # daemon process. Env-gated so a clean run never
                # imports the fault module here.
                from jepsen_tpu.serve import faults as _serve_faults
                _serve_faults.fire("prep")
            for gi, g in enumerate(groups):
                if stop.is_set():
                    return
                t0 = _time.monotonic()
                built = _union_pack_group(sa, g, max_slots)
                if built is None:
                    _put(("decline", gi, None))
                    return
                ret_flat, ops_flat, key_W, key_R, offsets, W = built
                M = 1 << W
                if not (_fast_ok(sa.S_pad, W, M, sa.memo_u.n_ops)
                        and _pallas_fits(sa.S_pad, M, sa.memo_u.n_ops)):
                    _put(("decline", gi, None))
                    return
                prep = reach_batch.prepare_returns_batch(
                    P,
                    [ret_flat[offsets[k]:offsets[k + 1]]
                     for k in range(len(g))],
                    [ops_flat[offsets[k]:offsets[k + 1]]
                     for k in range(len(g))],
                    M)
                prep_wall[0] += _time.monotonic() - t0
                if not _put(("group", gi, (prep, key_W, key_R))):
                    return
                queue_hwm[0] = max(queue_hwm[0], q.qsize())
            _put(("done", -1, None))
        # jtlint: ok fallback — error tuple forwarded to the consumer, which re-raises
        except BaseException as e:                      # noqa: BLE001
            _put(("error", -1, e))

    dead = np.full(n_live, -1, np.int64)
    key_W_full = np.zeros(n_live, np.int32)
    key_R_full = np.zeros(n_live, np.int32)
    st = _LockstepDispatchState(devices, dead)
    gdiags: List[dict] = []
    stall_s = dispatch_s = 0.0
    failure: Optional[Tuple[str, Any]] = None

    th = threading.Thread(target=_producer, name="jepsen-stream-prep",
                          daemon=True)
    th.start()
    try:
        while True:
            t0 = _time.monotonic()
            kind, gi, payload = q.get()
            stall_s += _time.monotonic() - t0
            if kind == "done":
                break
            if kind in ("decline", "error"):
                failure = (kind, payload)
                break
            prep, key_W, key_R = payload
            g = groups[gi]
            t0 = _time.monotonic()
            di, sp = st.place(gi, g, prep)
            sp["streamed"] = True
            with obs.span("lockstep.dispatch", **sp):
                fl = reach_batch.dispatch_prepared(prep)
            dispatch_s += _time.monotonic() - t0
            gdiags.append(st.admit(g, fl, di))
            idx = np.asarray(g, np.int64)
            key_W_full[idx] = key_W
            key_R_full[idx] = key_R
            st.drain(st.depth)
        if failure is None:
            st.drain(0)
    finally:
        stop.set()
        try:
            while True:
                q.get_nowait()
        # jtlint: ok fallback — shutdown drain of the prep queue
        except _queue.Empty:
            pass
        th.join(timeout=30.0)
        if th.is_alive():
            # producer stuck inside a native pack: it is a daemon and
            # touches only its own buffers (plus the cumulative
            # sa.pack_s accounting), so abandoning it is safe — but a
            # leaked thread racing the synchronous fallback's packing
            # must never be invisible
            obs.count("prep.thread_abandoned")
            obs.decision("stream-prep", "abandoned-thread",
                         groups=len(groups))
            logging.getLogger("jepsen.reach").warning(
                "streaming prep thread still running after 30s join; "
                "abandoning it (daemon) and continuing")
    if failure is not None:
        kind, err = failure
        cause = type(err).__name__ if kind == "error" else "declined"
        # the ISSUE-mandated record: a prep-thread failure degrades to
        # the synchronous path exactly once, never silently
        obs.engine_fallback("stream-prep", cause, groups=len(groups))
        if kind == "error":
            logging.getLogger("jepsen.reach").warning(
                "streaming prep failed (%r); falling back to the "
                "synchronous lockstep path", err, exc_info=err)
        return None
    hidden_s = max(0.0, prep_wall[0] - stall_s)
    _lockstep_accounting(gdiags, prep_wall[0], hidden_s, stall_s,
                         dispatch_s, st.fetch_s, "stream",
                         queue_hwm[0], diag, st.mesh_info(pad_lanes),
                         st.fetch_degraded)
    obs.count("prep.streamed_groups", len(gdiags))
    return dead, key_W_full, key_R_full


def _check_lockstep_stream(engine: str, model: Model,
                           packed_list: Sequence[h.PackedHistory],
                           live: Sequence[int], sa: "_UnionPrepA",
                           max_states: int, max_slots: int,
                           max_dense: int, group: int,
                           diag: Optional[dict], t0: float,
                           devices: Optional[Sequence] = None
                           ) -> Optional[List[Dict[str, Any]]]:
    """Run the streaming lockstep pipeline end to end: plan bucket
    groups from the per-key return counts (every non-crashed entry
    returns exactly once, so ``n_ok`` IS the return count — known
    before any native build), stream prep→dispatch, assemble results.
    With ``devices`` the planned groups are lane-sharded over the mesh
    (:func:`reach_batch.shard_groups_for_mesh`) and the dispatcher
    multi-queues them round-robin across chips. Returns None when
    there is nothing to overlap (single group) or the pipeline fell
    back — the caller then runs the synchronous path on the same
    stage A, so verdicts are bit-identical."""
    from jepsen_tpu.checkers import reach_batch

    lens = [int(packed_list[i].n_ok) for i in live]
    # the planner's floor only needs a width HINT (a coarser floor
    # splits small keys into more groups — suboptimal packing, never
    # incorrect); the true union W is only known after native packing
    groups = reach_batch.plan_buckets(lens, max_slots, group=group)
    pad_lanes = 0
    if devices is not None and len(devices) > 1:
        groups, pad_lanes = reach_batch.shard_groups_for_mesh(
            groups, len(devices))
    if len(groups) < 2:
        return None         # nothing to hide — synchronous is simpler
    try:
        r = _dispatch_lockstep_stream(sa, groups, max_slots, len(live),
                                      diag, devices=devices,
                                      pad_lanes=pad_lanes)
    except Exception as e:                              # noqa: BLE001
        # dispatch-side failure: recorded, then the synchronous path
        # gets its chance (and takes the existing per-history
        # fallbacks if it fails the same way)
        obs.engine_fallback("stream-prep", type(e).__name__,
                            groups=len(groups))
        logging.getLogger("jepsen.reach").warning(
            "streaming lockstep dispatch failed (%r); retrying the "
            "synchronous path", e)
        return None
    if r is None:
        return None
    dead, key_W, key_R = r
    elapsed = _time.monotonic() - t0
    return _union_results_parts(engine, model, packed_list, live, dead,
                                sa.memo_u, key_W, key_R,
                                sa.drop_per_key(), elapsed, max_states,
                                max_slots, max_dense)


def _check_many_lockstep(model: Model,
                         packed_list: Sequence[h.PackedHistory],
                         max_states: int, max_slots: int,
                         max_dense: int, t0: float,
                         group: int = 0,
                         diag: Optional[dict] = None,
                         u_box: Optional[dict] = None
                         ) -> Optional[List[Dict[str, Any]]]:
    """Bucketed-lockstep fast lane for :func:`check_many` — the
    production path for ragged ``independent`` batches: ONE union
    memo + ONE native preprocessing call (as the keyed lane), then
    length-bucketed lane packing (:func:`reach_batch.plan_buckets`) so
    a long key never forces short keys through its padding, pipelined
    group dispatch, and per-geometry compiled kernels cached across
    groups. Aggregate throughput beats the keyed kernel because H keys
    advance per lockstep step instead of one — the flat keyed stream
    pays the per-issue latency wall once per RETURN, this lane once
    per step. Returns the results list, or None to fall through to the
    keyed kernel / vmapped XLA paths (no native lib, union explosion,
    budget overflow, kernel failure)."""
    from jepsen_tpu.checkers import preproc_native

    if not (_use_pallas() and preproc_native.available()):
        return None
    live = [i for i, p in enumerate(packed_list) if p.n and p.n_ok]
    if len(live) < 2:
        return None
    if sum(packed_list[i].n_ok for i in live) < _PALLAS_MIN_RETURNS:
        return None
    if _stream_prep_enabled():
        sa = _union_stage_a_shared(model, packed_list, live, max_states,
                                   u_box)
        if sa is None:
            if u_box is not None:
                u_box["u"] = None       # stage A failure implies no u
            return None
        out = _check_lockstep_stream(
            "reach-lockstep", model, packed_list, live, sa, max_states,
            max_slots, max_dense, group or _BATCH_GROUP, diag, t0)
        if out is not None:
            return out
    u = _union_prep_shared(model, packed_list, live, max_states,
                           max_slots, u_box)
    if u is None:
        return None
    from jepsen_tpu.checkers import reach_batch
    (_memo_u, _S_pad, P, W, M, ret_flat, ops_flat, _key_W, key_R,
     offsets, _opid_cat, _crs_cat, _offs, _noop_op) = u
    groups = reach_batch.plan_buckets(
        [int(r) for r in key_R], W, group=group or _BATCH_GROUP)
    sa_box = (u_box or {}).get("sa")
    try:
        dead = _dispatch_lockstep_groups(
            P, ret_flat, ops_flat, offsets, groups, M, len(live), diag,
            prep_base_s=sa_box.pack_s if sa_box is not None else 0.0)
    except Exception as e:                              # noqa: BLE001
        _warn_pallas_failed(f"lockstep: {e!r}")
        return None
    elapsed = _time.monotonic() - t0
    return _union_results("reach-lockstep", model, packed_list, live,
                          dead, u, elapsed, max_states, max_slots,
                          max_dense)


class StagedMany:
    """A staged-but-uncollected :func:`check_many` lockstep batch: the
    union prep ran, every dispatch group's walk is QUEUED on device
    (host pack + puts + kernel launches paid), and nothing has been
    fetched. Produced by :func:`stage_check_many`; a serve lane holds
    K of these in flight so group k+1's stage overlaps group k's
    device walk. ``collect()`` FIFO-fetches the few verdict words and
    assembles results exactly as the synchronous lockstep lane would —
    bit-identical verdicts by construction (same kernels, same
    ``_union_results`` assembly). A collect-side device error
    propagates to the caller's recovery ladder; the retained host
    operands make the re-run safe."""

    __slots__ = ("model", "packed_list", "live", "u", "st", "gdiags",
                 "prep_s", "dispatch_s", "t0", "max_states",
                 "max_slots", "max_dense", "dead")

    def __init__(self, model, packed_list, live, u, st, gdiags,
                 prep_s, dispatch_s, t0, max_states, max_slots,
                 max_dense, dead):
        self.model = model
        self.packed_list = packed_list
        self.live = live
        self.u = u
        self.st = st
        self.gdiags = gdiags
        self.prep_s = prep_s
        self.dispatch_s = dispatch_s
        self.t0 = t0
        self.max_states = max_states
        self.max_slots = max_slots
        self.max_dense = max_dense
        self.dead = dead

    def ready(self) -> bool:
        """True when every staged group's device results are resident
        (collect would not block on the walk)."""
        return all(dispatch_core.inflight_ready(fl)
                   for _g, fl, _di in self.st.inflight)

    def collect(self) -> List[Dict[str, Any]]:
        """Fetch verdicts and assemble per-history results (the
        accounting tail the synchronous scheduler emits per
        dispatch)."""
        self.st.collect(0)
        _lockstep_accounting(self.gdiags, self.prep_s, 0.0, 0.0,
                             self.dispatch_s, self.st.fetch_s,
                             "pipeline", 0, None, self.st.mesh_info(0),
                             self.st.fetch_degraded)
        elapsed = _time.monotonic() - self.t0
        return _union_results("reach-lockstep", self.model,
                              self.packed_list, self.live, self.dead,
                              self.u, elapsed, self.max_states,
                              self.max_slots, self.max_dense)


def stage_check_many(model: Model,
                     packed_list: Sequence[h.PackedHistory], *,
                     max_states: int = 100_000, max_slots: int = 20,
                     max_dense: int = 1 << 22,
                     group: int = 0
                     ) -> Optional["StagedMany | StagedVmapped"]:
    """STAGE half of the pipelined :func:`check_many` lockstep route:
    union prep + bucketed lane packing + every dispatch group's walk
    queued on device, nothing fetched. Returns a :class:`StagedMany`
    to collect later, or None when the batch is not stageable (gates
    closed, too few live histories/returns, union prep declined) —
    the caller then runs the ordinary blocking chain, which redoes
    nothing but the cheap gate checks. A failure AFTER some groups
    dispatched drains them best-effort and declines, so a staged probe
    can never leak in-flight device work."""
    from jepsen_tpu.checkers import preproc_native, reach_batch

    if not dispatch_core.pipeline_enabled():
        return None
    if not (_use_pallas() and preproc_native.available()):
        # no Pallas lockstep lane on this backend: stage the vmapped
        # fast batch the blocking chain would route instead (the
        # XLA:CPU serve path — async dispatch overlaps there too)
        return _stage_many_vmapped(model, packed_list,
                                   max_states=max_states,
                                   max_slots=max_slots,
                                   max_dense=max_dense)
    live = [i for i, p in enumerate(packed_list) if p.n and p.n_ok]
    if len(live) < 2:
        return None
    if sum(packed_list[i].n_ok for i in live) < _PALLAS_MIN_RETURNS:
        return None
    _ensure_persistent_caches()
    t0 = _time.monotonic()
    u = _union_prep_shared(model, packed_list, live, max_states,
                           max_slots, None)
    if u is None:
        return None
    (_memo_u, _S_pad, P, W, M, ret_flat, ops_flat, _key_W, key_R,
     offsets, _opid_cat, _crs_cat, _offs, _noop_op) = u
    groups = reach_batch.plan_buckets(
        [int(r) for r in key_R], W, group=group or _BATCH_GROUP)
    dead = np.full(len(live), -1, np.int64)
    st = _LockstepDispatchState(None, dead)
    gdiags: List[dict] = []
    prep_s = dispatch_s = 0.0
    try:
        for gi, g in enumerate(groups):
            ta = _time.monotonic()
            with obs.span("lockstep.prep", lanes=len(g)):
                prep = reach_batch.prepare_returns_batch(
                    P,
                    [ret_flat[offsets[k]:offsets[k + 1]] for k in g],
                    [ops_flat[offsets[k]:offsets[k + 1]] for k in g],
                    M)
            tb = _time.monotonic()
            prep_s += tb - ta
            gdiags.append(st.stage(gi, g, prep,
                                   reach_batch.dispatch_prepared))
            dispatch_s += _time.monotonic() - tb
    except Exception as e:                              # noqa: BLE001
        # jtlint: ok fallback — stage probe declines; the caller's
        # blocking chain re-runs the batch with its own fallback
        # ladder, so nothing is lost but the attempted launches
        obs.count("pipeline.stage_error")
        _warn_pallas_failed(f"stage: {e!r}")
        try:
            st.collect(0)
        # jtlint: ok fallback — draining a poisoned probe is best-effort; the blocking re-run owns the verdicts
        except Exception:                               # noqa: BLE001
            pass
        return None
    return StagedMany(model, packed_list, live, u, st, gdiags, prep_s,
                      dispatch_s, t0, max_states, max_slots, max_dense,
                      dead)


def _stage_many_vmapped(model: Model,
                        packed_list: Sequence[h.PackedHistory], *,
                        max_states: int, max_slots: int,
                        max_dense: int) -> Optional[StagedVmapped]:
    """STAGE half of the vmapped-XLA :func:`check_many` fast batch:
    per-key prep + the one batched walk launched, fetch deferred.
    Mirrors ``check_many``'s single-device route gates EXACTLY —
    declines whenever an earlier route (Pallas lockstep/keyed), the
    slow event-walk tail, or an overflow would answer instead, so a
    staged batch and the blocking re-run can never disagree on either
    route or verdict. Routine budget overflows decline silently (the
    blocking chain re-raises them under its own per-history fallback
    ladder); only a genuine launch crash counts
    ``pipeline.stage_error``."""
    from jepsen_tpu.checkers.events import ConcurrencyOverflow
    from jepsen_tpu.models.memo import StateExplosion

    if len([i for i, p in enumerate(packed_list)
            if p.n and p.n_ok]) < 2:
        return None
    _ensure_persistent_caches()
    t0 = _time.monotonic()
    try:
        _seed_union_memo(model, [p for p in packed_list
                                 if p.n and p.n_ok], max_states)
        preps = []
        for packed in packed_list:
            if packed.n == 0 or packed.n_ok == 0:
                preps.append(None)
                continue
            preps.append(_prep(model, packed, max_states=max_states,
                               max_slots=max_slots,
                               max_dense=max_dense))
    # jtlint: ok fallback — routine budget overflow: the stage probe declines; the blocking re-run re-raises it under its own recorded ladder
    except (DenseOverflow, ConcurrencyOverflow, StateExplosion):
        return None
    live = [i for i, p in enumerate(preps) if p is not None]
    if not live:
        return None
    results: List[Optional[Dict[str, Any]]] = [
        None if p is not None else
        {"valid": True, "engine": "reach-batch", "events": 0,
         "time-s": 0.0}
        for p in preps]
    S_pad = max(p[3] for i, p in enumerate(preps) if p is not None)
    W = max(max(preps[i][1].W, 1) for i in live)
    M = 1 << W
    if S_pad * M > max_dense:
        return None
    O_pad = max(preps[i][0].n_ops for i in live)
    if not _fast_ok(S_pad, W, M, O_pad):
        return None
    rss = [ev.returns_view(preps[i][1]) for i in live]
    if (_use_pallas()
            and sum(r.n_returns for r in rss) >= _PALLAS_MIN_RETURNS):
        return None                     # keyed kernel would answer
    try:
        return _vmapped_fast_launch(preps, live, results, rss,
                                    packed_list, S_pad, O_pad, W, M,
                                    t0)
    except Exception as e:                              # noqa: BLE001
        # jtlint: ok fallback — stage probe declines; the blocking
        # chain re-runs the batch under its own fallback ladder
        obs.count("pipeline.stage_error")
        logging.getLogger("jepsen.reach").warning(
            "vmapped stage failed (%r); declining to blocking path", e)
        return None


def _check_many_mesh_lockstep(model: Model,
                              packed_list: Sequence[h.PackedHistory],
                              max_states: int, max_slots: int,
                              max_dense: int, devices: Sequence,
                              t0: float, group: int = 0,
                              diag: Optional[dict] = None,
                              u_box: Optional[dict] = None
                              ) -> Optional[List[Dict[str, Any]]]:
    """Device-sharded lockstep lane for the MESH path of
    :func:`check_many` (the ISSUE 4 tentpole): the same union stage A
    and bucketed lane packing as the single-chip lockstep lane, with
    the lockstep LANE axis sharded over ``devices`` — dispatch groups
    are split into per-device lane blocks until every chip holds one
    (:func:`reach_batch.shard_groups_for_mesh`; pad lanes replicate a
    real lane, so verdicts stay exact) and placed round-robin in the
    canonical mesh order, while the streaming prep thread multi-queues
    groups so device k walks group g as device j walks group g+1.
    Returns the results list, or None to fall through to the keyed
    mesh-union lane (gates closed: ``JEPSEN_TPU_NO_MESH_LOCKSTEP=1``,
    no Pallas, no native lib, union explosion/budget overflow, too few
    returns, an unsplittable batch). A dispatch failure ON the mesh
    (compile failure, padding overflow, device placement) records
    exactly ONE ``mesh-lockstep`` fallback in the obs ledger and
    re-runs the batch on the SINGLE-DEVICE lockstep lane — asking for
    more chips must degrade to fewer chips on the SAME engine, never
    silently to the keyed kernel."""
    from jepsen_tpu.checkers import preproc_native, reach_batch

    if not reach_batch.mesh_lockstep_enabled():
        return None
    if not (_use_pallas() and preproc_native.available()):
        return None
    live = [i for i, p in enumerate(packed_list) if p.n and p.n_ok]
    if len(live) < 2:
        return None
    if sum(packed_list[i].n_ok for i in live) < _PALLAS_MIN_RETURNS:
        return None
    from jepsen_tpu import parallel as par

    # the same 1-D mesh plumbing as the keyed lanes
    # (_key_axis_shardings): lane blocks land in the mesh's ravel
    # order, so block k and NamedSharding shard k pick the same chip
    devs = par.device_order(list(devices), "lanes")
    sa = _union_stage_a_shared(model, packed_list, live, max_states,
                               u_box)
    if sa is None:
        if u_box is not None:
            u_box["u"] = None       # stage A failure implies no u
        return None
    try:
        if _stream_prep_enabled():
            out = _check_lockstep_stream(
                "reach-lockstep-mesh", model, packed_list, live, sa,
                max_states, max_slots, max_dense,
                group or _BATCH_GROUP, diag, t0, devices=devs)
            if out is not None:
                return out
        u = _union_prep_shared(model, packed_list, live, max_states,
                               max_slots, u_box)
        if u is None:
            return None
        (_memo_u, _S_pad, P, W, M, ret_flat, ops_flat, _key_W, key_R,
         offsets, _opid_cat, _crs_cat, _offs, _noop_op) = u
        groups = reach_batch.plan_buckets(
            [int(r) for r in key_R], W, group=group or _BATCH_GROUP)
        groups, pad_lanes = reach_batch.shard_groups_for_mesh(
            groups, len(devs))
        if len(groups) < 2:
            return None         # unsplittable: nothing to shard
        sa_box = (u_box or {}).get("sa")
        dead = _dispatch_lockstep_groups(
            P, ret_flat, ops_flat, offsets, groups, M, len(live), diag,
            prep_base_s=sa_box.pack_s if sa_box is not None else 0.0,
            devices=devs, pad_lanes=pad_lanes)
    except Exception as e:                              # noqa: BLE001
        _warn_pallas_failed(f"mesh-lockstep: {e!r}")
        obs.engine_fallback("mesh-lockstep", type(e).__name__,
                            histories=len(live), devices=len(devs))
        return _check_many_lockstep(model, packed_list, max_states,
                                    max_slots, max_dense, t0,
                                    group=group, diag=diag,
                                    u_box=u_box)
    elapsed = _time.monotonic() - t0
    return _union_results("reach-lockstep-mesh", model, packed_list,
                          live, dead, u, elapsed, max_states,
                          max_slots, max_dense)


def _key_axis_shardings(devices: Sequence, n_keys: int):
    """Mesh + (sharded, replicated) NamedShardings for a leading key
    axis, and the pad count making ``n_keys`` device-divisible —
    shared by both mesh branches of :func:`check_many`."""
    from jax.sharding import NamedSharding, PartitionSpec

    from jepsen_tpu import parallel as par

    m = par.mesh("keys", list(devices))
    n_dev = len(devices)
    pad = -(-n_keys // n_dev) * n_dev - n_keys
    return (NamedSharding(m, PartitionSpec("keys")),
            NamedSharding(m, PartitionSpec()), pad)


def _check_many_mesh_native(model: Model,
                            packed_list: Sequence[h.PackedHistory],
                            max_states: int, max_slots: int,
                            max_dense: int, devices: Sequence,
                            t0: float, u_box: Optional[dict] = None
                            ) -> Optional[List[Dict[str, Any]]]:
    """Union-native fast lane for the MESH path of :func:`check_many`:
    the same ONE-memo + ONE-native-build prep as
    :func:`_check_many_native`, marshaled into the key-padded arrays
    the sharded vmapped XLA walk consumes — replacing the per-key
    memo/BFS/event-build pipeline (~2 s of serial host time at 4096
    keys, paid by EVERY process in a multi-host run). Valid keys are
    answered from the union geometry; the rare failed key decodes
    exactly via :func:`check_packed`. Returns None to fall through to
    the general mesh path (no native lib, union explosion, budget
    overflow)."""
    import jax
    import jax.numpy as jnp

    from jepsen_tpu.checkers import preproc_native

    if not preproc_native.available():
        return None
    live = [i for i, p in enumerate(packed_list) if p.n and p.n_ok]
    if len(live) < 2:
        return None
    # reuse the mesh-lockstep attempt's prep: a cached full u is
    # directly valid (its gates are stricter), and a cached stage A
    # skips re-paying the union BFS when only the Pallas gate failed
    u = (u_box or {}).get("u")
    if u is None:
        sa = _union_stage_a_shared(model, packed_list, live, max_states,
                                   u_box)
        if sa is None:
            return None
        u = _union_prep(model, packed_list, live, max_states, max_slots,
                        need_pallas=False, stage_a=sa)
    if u is None:
        return None
    (memo_u, S_pad, P, W, M, ret_flat, ops_flat, key_W, key_R,
     offsets, opid_cat, crs_cat, offs, noop_op) = u
    if S_pad * M > max_dense:
        return None
    K_live = len(live)
    R_pad = max(64, _bucket(int(key_R.max()), _UNROLL))
    slot_np = np.full((K_live, R_pad), -1, np.int32)
    ops_np = np.full((K_live, R_pad, W), -1, np.int32)
    for k in range(K_live):
        lo, hi = int(offsets[k]), int(offsets[k + 1])
        slot_np[k, :hi - lo] = ret_flat[lo:hi]
        ops_np[k, :hi - lo] = ops_flat[lo:hi]
    R0 = np.zeros((S_pad, M), bool)
    R0[0, 0] = True
    xor_cols, bitmask = _xor_bitmask(W, M)
    skey, srep, pad = _key_axis_shardings(devices, K_live)

    def padk(a):
        return np.concatenate(
            [a, np.repeat(a[:1], pad, axis=0)]) if pad else a

    slot_b = jax.device_put(padk(slot_np), skey)
    ops_b = jax.device_put(padk(ops_np), skey)
    P_dev = jax.device_put(P, srep)
    R0_b = jax.device_put(R0, srep)
    xc, bm = jnp.asarray(xor_cols), jnp.asarray(bitmask)
    _ptrs, _, alives, _R_blocks = _jitted_walk_returns_batch_shared()(
        P_dev, xc, bm, slot_b, ops_b, R0_b)
    elapsed = _time.monotonic() - t0
    alives = _fetch(alives)[:K_live]
    drop_cat = (crs_cat & noop_op[opid_cat]).astype(np.int64)
    drop_per_key = np.add.reduceat(drop_cat, offs[:-1])
    results: List[Optional[Dict[str, Any]]] = [
        {"valid": True, "engine": "reach-batch", "events": 0,
         "time-s": 0.0} if (packed_list[i].n == 0
                            or packed_list[i].n_ok == 0) else None
        for i in range(len(packed_list))]
    for k, i in enumerate(live):
        p = packed_list[i]
        if bool(alives[k]):
            results[i] = _union_valid_result(
                "reach-batch", p, int(drop_per_key[k]), int(key_R[k]),
                int(key_W[k]), memo_u.n_states, elapsed)
        else:
            # rare: exact single-history decode with full witness
            results[i] = check_packed(model, p, max_states=max_states,
                                      max_slots=max_slots,
                                      max_dense=max_dense)
    return results  # type: ignore[return-value]


class StagedVmapped:
    """A staged-but-uncollected vmapped-XLA :func:`check_many` fast
    batch: per-key prep ran and the ONE batched returns-walk call is
    queued on device (async dispatch — CPU included), nothing fetched.
    The non-Pallas twin of :class:`StagedMany`, so the serve lanes'
    K-deep window overlaps host pack with device walks on every
    backend the blocking route serves. ``collect()`` fetches the few
    verdict words and assembles results exactly as the blocking branch
    would — it IS the blocking branch's tail (one shared
    implementation, :func:`_vmapped_fast_launch`), so verdicts are
    bit-identical by construction. A collect-side device error
    propagates to the caller's recovery ladder."""

    __slots__ = ("futures", "_collect")

    def __init__(self, futures, collect_fn):
        self.futures = futures
        self._collect = collect_fn

    def ready(self) -> bool:
        """True when the batched walk's verdict words are resident
        (collect would not block on the device)."""
        return all(dispatch_core.poll_ready(f) for f in self.futures)

    def collect(self) -> List[Dict[str, Any]]:
        return self._collect()


def _vmapped_fast_launch(preps, live, results, rss, packed_list,
                         S_pad, O_pad, W, M, t0,
                         devices: Optional[Sequence] = None
                         ) -> "StagedVmapped":
    """LAUNCH half of the vmapped fast-path returns walk — host
    operand build + the one batched device call, fetch deferred into
    the returned handle's ``collect()``. :func:`check_many` calls
    launch+collect back-to-back (the historical blocking branch);
    :func:`stage_check_many` keeps the handle open so a serve lane
    can stage the next group while this one walks."""
    import jax.numpy as jnp

    n_dev = len(devices) if devices is not None else 1
    Ps, R0s = [], []
    for i in live:
        Ps.append(_build_P(preps[i][0], S_pad, O_pad))
        R0 = np.zeros((S_pad, M), bool)
        R0[0, 0] = True
        R0s.append(R0)
    # shared-alphabet fast path: uniform workloads produce the
    # same P for every key — skip the per-key matrix batch
    shared = all((Ps[k] == Ps[0]).all() for k in range(1, len(Ps)))
    R_pad = max(64, _bucket(max(r.n_returns for r in rss), _UNROLL))
    rss = [ev.pad_returns(r, R_pad, W) for r in rss]
    xor_cols, bitmask = _xor_bitmask(W, M)
    xc, bm = jnp.asarray(xor_cols), jnp.asarray(bitmask)
    slot_np = np.stack([r.ret_slot for r in rss])
    ops_np = np.stack([r.slot_ops for r in rss])
    Ps_np = None if shared else np.stack(Ps)
    R0s_np = np.stack(R0s)
    K_live = len(rss)
    if n_dev > 1:
        # key-axis DP over the mesh: pad the key count to a
        # multiple of the device count (pad keys replay key 0,
        # whose verdict is discarded), shard the leading axis,
        # replicate the shared operands
        import jax
        skey, srep, pad = _key_axis_shardings(devices, K_live)

        def padk(a):
            return np.concatenate(
                [a, np.repeat(a[:1], pad, axis=0)]) if pad else a

        slot_b = jax.device_put(padk(slot_np), skey)
        ops_b = jax.device_put(padk(ops_np), skey)
        if shared:
            Ps_dev = jax.device_put(Ps[0], srep)
            R0_b = jax.device_put(R0s[0], srep)
        else:
            Ps_dev = jax.device_put(padk(Ps_np), skey)
            R0_b = jax.device_put(padk(R0s_np), skey)
    else:
        slot_b = jnp.asarray(slot_np)
        ops_b = jnp.asarray(ops_np)
        Ps_dev = jnp.asarray(Ps[0] if shared else Ps_np)
        R0_b = jnp.asarray(R0s[0] if shared else R0s_np)
    if shared:
        ptrs, _, alives, R_blocks = \
            _jitted_walk_returns_batch_shared()(
                Ps_dev, xc, bm, slot_b, ops_b, R0_b)
    else:
        ptrs, _, alives, R_blocks = _jitted_walk_returns_batch()(
            Ps_dev, xc, bm, slot_b, ops_b, R0_b)

    def _collect() -> List[Dict[str, Any]]:
        elapsed = _time.monotonic() - t0
        ptrs_np = _fetch(ptrs)[:K_live]
        alives_np = _fetch(alives)[:K_live]
        R_blocks_np = None          # fetched lazily, only on failures
        for k, i in enumerate(live):
            memo, stream = preps[i][0], preps[i][1]
            if bool(alives_np[k]):
                results[i] = _result_valid("reach-batch", stream, memo,
                                           elapsed)
            else:
                if R_blocks_np is None:
                    R_blocks_np = _fetch(R_blocks)
                Pk = (jnp.asarray(Ps[0]) if shared
                      else jnp.asarray(Ps_np[k]))
                dead_event = _refine_dead(Pk, xc, bm, rss[k],
                                          int(ptrs_np[k]),
                                          jnp.asarray(R_blocks_np[k]))
                results[i] = _result_invalid(
                    "reach-batch", stream, memo, packed_list[i],
                    dead_event, elapsed)
                dead_ret = int(np.searchsorted(
                    rss[k].ret_event[:rss[k].n_returns], dead_event))
                _attach_witness(results[i], memo, rss[k],
                                Ps[k], S_pad, M, W, dead_ret,
                                packed_list[i])
        return results  # type: ignore[return-value]

    return StagedVmapped([ptrs, alives], _collect)


def check_many(model: Model, packed_list: Sequence[h.PackedHistory], *,
               max_states: int = 100_000, max_slots: int = 20,
               max_dense: int = 1 << 22,
               devices: Optional[Sequence] = None,
               should_abort=None,
               group: int = 0,
               diag: Optional[dict] = None) -> List[Dict[str, Any]]:
    """Batched per-key checking (the ``independent`` checker's hot
    path). Single-chip route order: the bucketed LOCKSTEP lane
    (:func:`_check_many_lockstep` — groups of keys advance together,
    one return index per step), then the keyed flat-stream kernel,
    then one vmapped device call over all keys padded to common
    shapes. Keys whose history does not fit the dense engine raise;
    callers split those out first via :func:`fits`.

    With ``devices`` (>1), the MESH-LOCKSTEP lane runs first
    (:func:`_check_many_mesh_lockstep` — the lockstep lane axis
    sharded over the mesh, dispatch groups multi-queued per device),
    then the keyed mesh-union lane: the key axis sharded over a
    ``jax.sharding.Mesh`` — the data-parallel axis of SURVEY.md §2.4:
    per-key searches are independent, so the only cross-device traffic is
    the while-loop's all-reduced liveness test. ``should_abort`` is
    consulted once before the batched device dispatch (the batch is one
    call — per-key granularity would defeat its throughput); when it
    fires, every live key reports ``valid == "unknown"``. ``group``
    overrides the lockstep lanes' dispatch-group width (0 = default);
    ``diag`` (a dict, filled in place) receives the lockstep lane's
    per-group geometry, pack efficiency, kernel-cache counters, and —
    on a mesh — the per-device group counts and pad waste."""
    import jax.numpy as jnp

    _ensure_persistent_caches()
    t0 = _time.monotonic()
    if should_abort is not None and should_abort():
        return [{"valid": "unknown", "cause": "aborted",
                 "engine": "reach-batch"} for _ in packed_list]
    if devices is None or len(devices) <= 1:
        u_box: dict = {}        # one union prep shared by both lanes
        out = _check_many_lockstep(model, packed_list,
                                   max_states=max_states,
                                   max_slots=max_slots,
                                   max_dense=max_dense, t0=t0,
                                   group=group, diag=diag, u_box=u_box)
        if out is not None:
            obs.decision("reach-many", "route", cause="lockstep",
                         histories=len(packed_list))
            return out
        out = _check_many_native(model, packed_list,
                                 max_states=max_states,
                                 max_slots=max_slots,
                                 max_dense=max_dense, t0=t0,
                                 u_box=u_box)
        if out is not None:
            obs.decision("reach-many", "route", cause="keyed",
                         histories=len(packed_list))
            return out
    else:
        u_box = {}              # stage A shared across the mesh lanes
        out = _check_many_mesh_lockstep(model, packed_list, max_states,
                                        max_slots, max_dense, devices,
                                        t0, group=group, diag=diag,
                                        u_box=u_box)
        if out is not None:
            # a mesh dispatch failure degrades INSIDE the lane to the
            # single-device lockstep scheduler — name which one
            # answered so "more chips" never silently means "fewer"
            engines = {r.get("engine") for r in out}
            cause = ("mesh-lockstep"
                     if "reach-lockstep-mesh" in engines else
                     "lockstep")
            obs.decision("reach-many", "route", cause=cause,
                         histories=len(packed_list),
                         devices=len(devices))
            return out
        out = _check_many_mesh_native(model, packed_list, max_states,
                                      max_slots, max_dense, devices, t0,
                                      u_box=u_box)
        if out is not None:
            obs.decision("reach-many", "route", cause="mesh-union",
                         histories=len(packed_list))
            return out
    obs.decision("reach-many", "route", cause="vmapped-xla",
                 histories=len(packed_list))
    _seed_union_memo(model, [p for p in packed_list
                             if p.n and p.n_ok], max_states)
    preps = []
    for packed in packed_list:
        if packed.n == 0 or packed.n_ok == 0:
            preps.append(None)
            continue
        preps.append(_prep(model, packed, max_states=max_states,
                           max_slots=max_slots, max_dense=max_dense))
    live = [i for i, p in enumerate(preps) if p is not None]
    results: List[Optional[Dict[str, Any]]] = [
        None if p is not None else
        {"valid": True, "engine": "reach-batch", "events": 0, "time-s": 0.0}
        for p in preps]
    if live:
        S_pad = max(p[3] for i, p in enumerate(preps) if p is not None)
        W = max(max(preps[i][1].W, 1) for i in live)
        M = 1 << W
        if S_pad * M > max_dense:
            # padding every key to the common (S_pad, W) can overflow even
            # when each key fits individually
            raise DenseOverflow(
                f"batched dense config space {S_pad}x{M} exceeds budget "
                f"{max_dense}")
        O_pad = max(preps[i][0].n_ops for i in live)
        fast = _fast_ok(S_pad, W, M, O_pad)
        if fast:
            rss = [ev.returns_view(preps[i][1]) for i in live]
            total_returns = sum(r.n_returns for r in rss)
            n_dev = len(devices) if devices is not None else 1
            if (n_dev <= 1 and _use_pallas()
                    and total_returns >= _PALLAS_MIN_RETURNS):
                out = _check_many_keyed(model, rss, preps, live, results,
                                        packed_list, M, W, max_states, t0)
                if out is not None:
                    return out
            # launch + immediate collect: the blocking branch IS the
            # staged pair run back-to-back (one implementation, so the
            # serve lanes' pipelined verdicts cannot drift from these)
            return _vmapped_fast_launch(preps, live, results, rss,
                                        packed_list, S_pad, O_pad, W, M,
                                        t0, devices=devices).collect()
        E_pad = max(preps[i][1].E for i in live)
        Ts, kinds, slots, opids, R0s, slot0s, streams = \
            [], [], [], [], [], [], []
        for i in live:
            memo, stream, _, _, _ = preps[i]
            stream = ev.pad(stream, E_pad, W)
            streams.append(stream)
            Ts.append(_pad_table(memo, S_pad, O_pad))
            kinds.append(stream.kind)
            slots.append(stream.slot)
            opids.append(stream.opid)
            R0 = np.zeros((S_pad, M), bool)
            R0[0, 0] = True
            R0s.append(R0)
            slot0s.append(np.full(max(W, 1), -1, np.int32))
        ptrs, _, alives = _jitted_walk_batch()(
            jnp.asarray(np.stack(Ts)), jnp.asarray(np.stack(kinds)),
            jnp.asarray(np.stack(slots)), jnp.asarray(np.stack(opids)),
            jnp.asarray(np.stack(R0s)), jnp.asarray(np.stack(slot0s)))
        elapsed = _time.monotonic() - t0
        ptrs = np.asarray(ptrs)
        alives = np.asarray(alives)
        for k, i in enumerate(live):
            memo, stream = preps[i][0], streams[k]
            if bool(alives[k]):
                results[i] = _result_valid("reach-batch", stream, memo,
                                           elapsed)
            else:
                results[i] = _result_invalid(
                    "reach-batch", stream, memo, packed_list[i],
                    int(ptrs[k]) - 1, elapsed)
                _attach_witness_slow(results[i], memo, stream, Ts[k],
                                     S_pad, M, W, int(ptrs[k]) - 1,
                                     packed_list[i])
    return results  # type: ignore[return-value]


def check_chunked(model: Model, history: Sequence[Op] = (), *,
                  packed: Optional[h.PackedHistory] = None,
                  n_chunks: int = 8, max_states: int = 100_000,
                  max_slots: int = 20, max_dense: int = 1 << 22,
                  max_matrix: int = 1 << 26,
                  devices: Optional[Sequence] = None,
                  should_abort=None) -> Dict[str, Any]:
    """History-length-parallel check: split the RETURN stream into
    ``n_chunks`` chunks, compute each chunk's D×D boolean transfer matrix
    by running the returns walk over all D basis configs (vmapped over
    (chunk, basis); chunks shard across ``devices``), then fold the
    matrices on the host.

    The basis walk costs D× the sequential walk's work but has
    1/n_chunks the sequential depth, and the D-sized batch axis is what
    fills the device — the winning trade when D = S·2**W is small
    (register-family models). Requires ``D**2 <= max_matrix``."""
    import jax.numpy as jnp

    _ensure_persistent_caches()
    t0 = _time.monotonic()
    if packed is None:
        packed = h.pack(history)
    if packed.n == 0 or packed.n_ok == 0:
        return {"valid": True, "engine": "reach-chunked", "events": 0,
                "time-s": 0.0}
    memo, stream, T, S_pad, M = _prep(
        model, packed, max_states=max_states, max_slots=max_slots,
        max_dense=max_dense)
    D = S_pad * M
    if D * D > max_matrix:
        raise DenseOverflow(
            f"chunk transfer matrix {D}x{D} exceeds budget {max_matrix}")
    W = max(stream.W, 1)
    if not _fast_ok(S_pad, W, M, memo.n_ops):
        raise DenseOverflow("chunked basis walk exceeds fast-path budget")
    rs = ev.returns_view(stream)
    Rn = rs.n_returns
    n_chunks = max(1, min(n_chunks, max(Rn, 1)))
    per = -(-max(Rn, 1) // n_chunks)
    P_np = _build_P(memo, S_pad)
    # reachable-basis restriction (round 3): a forward sequential pass
    # checkpoints the reachable set at every chunk's left edge, so each
    # chunk's transfer matrix is computed over only the B ≤ D configs
    # that can actually enter it — cutting the engine's D× basis-work
    # multiplier to ~B̄×. On TPU the lane kernel's block-checkpoint
    # stream provides the boundaries in one dispatch (chunks align to
    # its 1024-return blocks); elsewhere chained XLA chunk walks carry
    # the set across devices with a single fetch at the end.
    # the restriction's extra round trips (forward chain + per-group
    # dispatches) only pay off when the full-basis walk's work —
    # Rn returns × D basis configs — is substantial; tiny histories
    # over small config spaces keep the one-call path
    restrict = Rn * D >= 1 << 20
    use_lane = (restrict and _use_pallas()
                and (devices is None or len(devices) <= 1)
                and _pallas_fits(S_pad, M, memo.n_ops)
                and Rn >= _PALLAS_MIN_RETURNS)
    if use_lane:
        from jepsen_tpu.checkers import reach_lane
        use_lane = W <= reach_lane._FAST_PASSES    # ckpt must be exact
    if use_lane:
        per = -(-per // reach_lane._BLOCK) * reach_lane._BLOCK
        n_chunks = -(-Rn // per)
    rs_p = ev.pad_returns(rs, n_chunks * per)
    ret_slot_c = rs_p.ret_slot.reshape(n_chunks, per)
    slot_ops_c = rs_p.slot_ops.reshape(n_chunks, per, W)
    xor_cols, bitmask = _xor_bitmask(W, M)
    if should_abort is not None and should_abort():
        return {"valid": "unknown", "cause": "aborted",
                "engine": "reach-chunked"}
    # forward pass → boundary sets [n_chunks, S, M] + final liveness
    R0_np = np.zeros((S_pad, M), bool)
    R0_np[0, 0] = True
    if use_lane:
        try:
            geom, _rsl, _opsl, host_args = reach_lane.pack_operands(
                P_np, rs_p.ret_slot, rs_p.slot_ops, R0_np)
            B_lane, _W, _M, _S, _O1, R_padl = geom
            run = reach_lane._lane_call(*geom, W, False)
            import jax
            ckpt, final = run(*jax.device_put(host_args))
            ckpt_np = np.asarray(ckpt) > 0.5       # [blocks, M, S]
            alive_fwd = bool(np.asarray(final).any())
            bounds = np.transpose(
                ckpt_np[(np.arange(n_chunks) * per) // B_lane],
                (0, 2, 1))                         # [n_chunks, S, M]
        except Exception as e:                      # noqa: BLE001
            _warn_pallas_failed(repr(e))
            use_lane = False
    if not restrict:
        # full basis, no forward pass: every config can enter every
        # chunk; the fold itself detects death
        bounds = np.ones((n_chunks, S_pad, M), bool)
        alive_fwd = True
    elif not use_lane:
        walk = _jitted_walk_returns()
        P_d, xc_d, bm_d = (jnp.asarray(P_np), jnp.asarray(xor_cols),
                           jnp.asarray(bitmask))
        # identity-pad each chunk to the walk's unroll grain (the
        # unrolled loop reads blocks of _UNROLL rows)
        L8 = -(-per // _UNROLL) * _UNROLL
        fslot = np.full((n_chunks, L8), -1, np.int32)
        fslot[:, :per] = ret_slot_c
        fops = np.full((n_chunks, L8, W), -1, np.int32)
        fops[:, :per] = slot_ops_c
        R_cur = jnp.asarray(R0_np)
        bound_devs, alive_devs = [], []
        for c in range(n_chunks):
            bound_devs.append(R_cur)
            _ptr, R_cur, alive_c, _blk = walk(
                P_d, xc_d, bm_d, jnp.asarray(fslot[c]),
                jnp.asarray(fops[c]), R_cur)
            alive_devs.append(alive_c)
        bounds = np.asarray(jnp.stack(bound_devs))  # one fetch
        alive_fwd = bool(np.asarray(alive_devs[-1]))
    if not alive_fwd:
        # dead: the last chunk entered with a non-empty set holds the
        # violation — localize below without computing any matrices
        nonempty = bounds.reshape(n_chunks, -1).any(axis=1)
        dead_chunk = int(np.nonzero(nonempty)[0][-1]) if nonempty.any() \
            else 0
        mats = None
    else:
        # restricted bases: one-hot rows over each boundary's configs.
        # Boundary sets are skewed (median ~4 configs, occasional ~30
        # on the headline history), so chunks are bucketed into narrow
        # and wide basis groups — padding every chunk to the global max
        # wasted ~8× of the basis-walk work.
        counts = bounds.reshape(n_chunks, -1).sum(axis=1)
        idxs = np.full((n_chunks, int(counts.max())), -1, np.int64)
        for c in range(n_chunks):
            flat = np.nonzero(bounds[c].reshape(-1))[0]
            idxs[c, :len(flat)] = flat

        def _basis_group(cs, B_pad):
            b = np.zeros((len(cs), B_pad, S_pad, M), bool)
            for j, c in enumerate(cs):
                flat = idxs[c][idxs[c] >= 0]
                b[j, np.arange(len(flat)), flat // M, flat % M] = True
            return b

        mats_by_chunk: List[Optional[np.ndarray]] = [None] * n_chunks
        if devices is not None and len(devices) > 1:
            # sharded path: one group (the chunk axis must stay whole
            # and evenly device-divisible)
            B_pad = max(8, _next_pow2(int(counts.max())))
            args = (jnp.asarray(P_np), jnp.asarray(xor_cols),
                    jnp.asarray(bitmask), jnp.asarray(ret_slot_c),
                    jnp.asarray(slot_ops_c),
                    jnp.asarray(_basis_group(range(n_chunks), B_pad)))
            from jepsen_tpu.parallel import chunked_transfer
            mats = chunked_transfer(args, devices)
            for c in range(n_chunks):
                mats_by_chunk[c] = mats[c]
        else:
            narrow = np.nonzero(counts <= 8)[0]
            wide = np.nonzero(counts > 8)[0]
            for cs in (narrow, wide):
                if not len(cs):
                    continue
                B_pad = max(8, _next_pow2(int(counts[cs].max())))
                R = _jitted_basis_returns()(
                    jnp.asarray(P_np), jnp.asarray(xor_cols),
                    jnp.asarray(bitmask), jnp.asarray(ret_slot_c[cs]),
                    jnp.asarray(slot_ops_c[cs]),
                    jnp.asarray(_basis_group(cs, B_pad)))
                Rn_np = np.asarray(R).reshape(len(cs), B_pad, D)
                for j, c in enumerate(cs):
                    mats_by_chunk[c] = Rn_np[j]
        # fold: v0 through each chunk's restricted transfer matrix
        v = np.zeros(D, bool)
        v[0] = True                              # state 0, mask 0
        dead_chunk = -1
        for c in range(n_chunks):
            flat = idxs[c][idxs[c] >= 0]
            active = v[flat]
            rows = mats_by_chunk[c][:len(flat)][active]
            v = rows.any(axis=0) if len(rows) else np.zeros(D, bool)
            if not v.any():
                dead_chunk = c
                break
    elapsed = _time.monotonic() - t0
    if dead_chunk < 0:
        out = _result_valid("reach-chunked", stream, memo, elapsed)
        out["chunks"] = n_chunks
        return out
    # exact localization: re-walk the failing prefix of returns
    # sequentially (bounded by dead_chunk+1 chunks of work), padded to an
    # unroll-aligned length with identity rows.
    hi = min((dead_chunk + 1) * per, rs_p.R)
    L = max(_UNROLL, -(-hi // _UNROLL) * _UNROLL)
    rs_loc = ev.pad_returns(
        ev.ReturnStream(ret_slot=rs_p.ret_slot[:hi],
                        slot_ops=rs_p.slot_ops[:hi],
                        ret_event=rs_p.ret_event[:hi],
                        ret_entry=rs_p.ret_entry[:hi],
                        W=W, n_returns=min(hi, rs.n_returns)), L)
    P_dev, xc, bm = (jnp.asarray(P_np), jnp.asarray(xor_cols),
                     jnp.asarray(bitmask))
    R0 = jnp.zeros((S_pad, M), jnp.bool_).at[0, 0].set(True)
    ptr, _, alive, R_block = _jitted_walk_returns()(
        P_dev, xc, bm, jnp.asarray(rs_loc.ret_slot),
        jnp.asarray(rs_loc.slot_ops), R0)
    dead_event = _refine_dead(P_dev, xc, bm, rs_loc, int(ptr), R_block)
    elapsed = _time.monotonic() - t0
    out = _result_invalid("reach-chunked", stream, memo, packed,
                          dead_event, elapsed)
    out["chunks"] = n_chunks
    return out
