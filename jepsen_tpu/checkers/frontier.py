"""Sparse batched-frontier linearizability engine — the device search for
high-concurrency histories.

Upstream analogue: ``knossos/src/knossos/linear.clj`` / ``wgl.clj``'s
explicit configuration sets (SURVEY.md §2.2) and SURVEY.md §7 phase 4's
original "batched frontier" design. The dense engine (:mod:`.reach`)
represents the reachable config set as a boolean tensor over
``states × 2**W`` and therefore dies (``DenseOverflow`` /
``ConcurrencyOverflow``) when ``W`` — the maximum number of concurrently
pending ops, which grows with every crashed ``info`` op a nemesis leaves
behind — exceeds ~20. This engine keeps the *sparse* set of reachable
configurations ⟨model-state, linearized-pending bitset⟩ as packed uint32
rows and advances all of them per history event with vectorized device
ops, so ``W`` may reach ``MAX_SLOTS`` (128) while memory scales with the
number of *reachable* configs, not ``2**W``:

- a config is one row of a ``uint32[F, K+1]`` array: ``K = ceil(W/32)``
  bitset words plus the model-state id (the row IS its dedup key);
- **fire** (linearize one more pending op) expands every config by every
  pending slot at once — a single gather through the flattened transition
  table — and the union is deduplicated by a lexicographic
  ``lax.sort`` over the row words followed by an adjacent-unique compact;
  passes repeat to a fixpoint (monotone, detected by the unique count);
- **return** keeps configs whose bitset linearized the returning op and
  clears that slot bit — an order-preserving filter (clearing one fixed
  bit in every surviving row preserves lexicographic order), so no
  re-sort is needed;
- an empty frontier at a return is a linearizability violation at exactly
  that event, the same minimal evidence knossos reports.

**Crashed-op quotient.** Knossos explores crashed (``info``) ops exactly:
each one holds a bitset slot forever, so ``k`` crashes contribute ``2**k``
linearized-subset combinations — the classic "info ops are expensive"
blowup. This engine canonicalizes them away: two *pending crashed* ops
with the same op id are interchangeable (neither ever returns, and firing
either produces the same successor state — live ops are never grouped,
since a live op's own return requires *its* bit), so a config only needs
the *count* of fired ops per ⟨crashed, op-id⟩ group. Canonical form packs
each group's fired bits into its lowest-ranked slots — computed on device
from the per-return pending map — collapsing ``2**k`` to
``∏ (group_size+1)`` while remaining exact.

The frontier capacity ``F`` is a static shape: the walk runs at a small
``F`` first and the host retries at 4× on overflow (knossos.linear
instead *dies* on config-set explosion; here only :class:`FrontierOverflow`
past ``max_frontier`` gives up, and the facade falls back to the CPU
searches). Exact, not probabilistic: rows are compared in full — no
fingerprint hashing — so verdicts cannot be corrupted by collisions.

The default ``max_frontier`` (131072 rows) admits dedup sorts of
~1.2M rows; the cap reflects memory budget. (Round 1 capped it at
16384 after ~590k-row ``lax.sort`` calls crashed a remote TPU worker
that is no longer used; on the current chip the large sorts are
unmeasured.)
"""
from __future__ import annotations

import functools
import time as _time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from jepsen_tpu import history as h
from jepsen_tpu import obs
from jepsen_tpu.checkers import events as ev
from jepsen_tpu.checkers import reach
from jepsen_tpu.models import Model
from jepsen_tpu.models.memo import Memo
from jepsen_tpu.op import Op

MAX_SLOTS = 128                 # bitset capped at 4 uint32 words

_STATUS_RUNNING = 0
_STATUS_DEAD = 1
_STATUS_OVERFLOW = 2
_STATUS_ABORT = 3              # host-side only (deadline / search control)


class FrontierOverflow(RuntimeError):
    """The reachable config set exceeds ``max_frontier`` rows; callers
    should fall back to another engine (upstream behaviour:
    knossos.linear dies on config-set explosion)."""


def _use_quotient() -> bool:
    """The dense product-space fast path (:mod:`.reach_q`) is on by
    default; ``JEPSEN_TPU_NO_QUOTIENT=1`` forces the sparse rows (used
    by tests that target the sparse walk itself)."""
    import os
    return not os.environ.get("JEPSEN_TPU_NO_QUOTIENT")


# -- device program ----------------------------------------------------------

def _sort_unique_compact(U, F, pack_bits: int = 0):
    """Dedup candidate rows ``U: u32[N, K+1]`` (invalid rows are all-ones):
    sort, adjacent-unique, compact the first ``F`` unique rows to the
    front. Returns ``(C: u32[F, K+1], count)`` where ``count`` may exceed
    ``F`` (overflow — compaction drops the excess, caller must re-run at
    a larger ``F``).

    With ``pack_bits = W > 0`` (feasible when ``K == 1`` and the state id
    fits ``32 - W`` bits — the common case), each row packs into ONE u32
    key ``(state << W) | word`` and the lexicographic multi-key sort
    becomes a single-key sort (~2× cheaper; the all-ones sentinel wraps
    to the all-ones key, so it still sorts last, and clearing a fixed
    bit in every survivor subtracts the same constant from every key, so
    :func:`_project`'s no-re-sort invariant is preserved)."""
    import jax.numpy as jnp
    from jax import lax

    N, K1 = U.shape
    if pack_bits and K1 == 2:
        key = (U[:, 1] << jnp.uint32(pack_bits)) | U[:, 0]
        ks = lax.sort(key)
        valid = ks != jnp.uint32(0xFFFFFFFF)
        differs = ks != jnp.roll(ks, 1)
        differs = differs.at[0].set(True)
        unique = valid & differs
        word = ks & jnp.uint32((1 << pack_bits) - 1)
        state = ks >> jnp.uint32(pack_bits)
        Us = jnp.where(valid[:, None], jnp.stack([word, state], axis=1),
                       jnp.uint32(0xFFFFFFFF))
    else:
        cols = lax.sort(tuple(U[:, i] for i in range(K1)), num_keys=K1)
        Us = jnp.stack(cols, axis=1)                   # u32[N, K+1] sorted
        valid = Us[:, K1 - 1] != jnp.uint32(0xFFFFFFFF)
        differs = jnp.any(Us != jnp.roll(Us, 1, axis=0), axis=1)
        differs = differs.at[0].set(True)
        unique = valid & differs
    count = jnp.sum(unique.astype(jnp.int32))
    pos = jnp.cumsum(unique.astype(jnp.int32)) - 1
    pos = jnp.where(unique & (pos < F), pos, F)        # F = drop row
    C = jnp.full((F, K1), jnp.uint32(0xFFFFFFFF))
    C = C.at[pos].set(Us, mode="drop")
    return C, count


def _extract_bits(U, word_idx, shift):
    """Per-slot fired bits of each row: ``bool[N, W]``."""
    import jax.numpy as jnp

    sel = U[:, word_idx]                               # u32[N, W]
    return ((sel >> shift.astype(jnp.uint32)) & jnp.uint32(1)) > 0


def _pack_bits(bits, bitmat):
    """Inverse of :func:`_extract_bits`: ``u32[N, K]`` mask words."""
    import jax.numpy as jnp

    W, K = bitmat.shape
    words = []
    for k in range(K):
        lo, hi = k * 32, min((k + 1) * 32, W)
        words.append(jnp.sum(bits[:, lo:hi].astype(jnp.uint32)
                             * bitmat[lo:hi, k][None, :], axis=1))
    return jnp.stack(words, axis=1)


def _slot_groups(ops_row, crashed_row):
    """Interchangeability structure at one return, from the pending map:
    ``grouped[w]`` (crashed slots participate), ``same[w, w']`` (same
    group: both crashed, same op id), ``rank[w]`` (w's index within its
    group, by slot order)."""
    import jax.numpy as jnp

    W = ops_row.shape[0]
    grouped = crashed_row & (ops_row >= 0)
    same = (grouped[:, None] & grouped[None, :]
            & (ops_row[:, None] == ops_row[None, :]))  # bool[W, W]
    rank = jnp.sum(same & (jnp.arange(W)[None, :] < jnp.arange(W)[:, None]),
                   axis=1)
    return grouped, same, rank


def _canonicalize(U, grouped, same, rank, word_idx, shift, bitmat):
    """Quotient rows by crashed-op interchangeability: within each group,
    repack the fired bits into the group's lowest-ranked slots (fired
    counts are all that matter — see module docstring). Live slots are
    untouched. Applied once per return: within a return the group
    structure is fixed and expansion preserves canonical form, but a slot
    freed by a live return may later host a *lower-numbered* member of an
    existing crashed group, shifting ranks."""
    import jax.numpy as jnp

    K1 = U.shape[1]
    K = K1 - 1
    valid = U[:, K] != jnp.uint32(0xFFFFFFFF)
    bits = _extract_bits(U, word_idx, shift)
    # counts[n, w] = fired bits in w's group (exact in f32: counts ≤ W)
    counts = jnp.dot(bits.astype(jnp.float32), same.astype(jnp.float32))
    canon = jnp.where(grouped[None, :],
                      rank[None, :].astype(jnp.float32) < counts, bits)
    out = jnp.concatenate([_pack_bits(canon, bitmat), U[:, K:]], axis=1)
    return jnp.where(valid[:, None], out, jnp.uint32(0xFFFFFFFF))


_BLOCK = 8                     # pending slots expanded per dedup round
                               # (sharded path; the single-device walk
                               # sizes rounds adaptively, see _round_blk)

# candidate-row budget for one expand round: at small F the whole slot
# axis fits one round — ONE dedup sort per closure pass instead of
# ceil(W/8) — while large F keeps rounds bounded (memory ~ budget·K1·4B)
_CAND_BUDGET = 1 << 21


def _round_blk(F: int, W: int) -> int:
    return max(_BLOCK, min(W, _CAND_BUDGET // max(F, 1)))


def _expand_block(C, pending, grouped, same, rank, T_flat, bitmat,
                  word_idx, shift, n_cols, lo, canon: bool,
                  blk_size: int = _BLOCK):
    """Canonical single-fire successors of every config through pending
    slots ``[lo, lo+blk_size)``: ``u32[F*blk_size, K+1]`` (illegal ones
    all-ones). Live pending slots fire when their bit is clear; grouped
    (crashed) slots fire only through the group's next canonical member
    (``rank == fired-count``, computed over the FULL slot axis — groups
    span blocks), so every successor of a canonical row is canonical and
    redundant interchangeable fires are never materialized.
    ``T_flat: i32[S*n_cols]`` is the flattened transition table."""
    import jax.numpy as jnp

    F, K1 = C.shape
    K = K1 - 1
    blk = slice(lo, lo + blk_size)
    pend_b = pending[blk]
    state = C[:, K].astype(jnp.int32)                  # -1 when invalid
    cvalid = state >= 0
    op_ok = pend_b >= 0
    o = jnp.where(op_ok, pend_b, 0)
    flat = jnp.clip(state, 0)[:, None] * n_cols + o[None, :]
    tgt = jnp.take(T_flat, flat)                       # i32[F, b]
    bits = _extract_bits(C, word_idx, shift)           # bool[F, W] (full)
    fireable = ~bits[:, blk]                           # live: bit clear
    if canon:
        counts = jnp.dot(bits.astype(jnp.float32),
                         same.astype(jnp.float32))     # f32[F, W]
        next_member = counts[:, blk] == rank[blk][None, :].astype(
            jnp.float32)
        fireable = jnp.where(grouped[blk][None, :], next_member, fireable)
    legal = cvalid[:, None] & op_ok[None, :] & fireable & (tgt >= 0)
    words = C[:, None, :K] | bitmat[None, blk, :]      # u32[F, b, K]
    cand = jnp.concatenate(
        [words, tgt[:, :, None].astype(jnp.uint32)], axis=2)
    cand = jnp.where(legal[:, :, None], cand, jnp.uint32(0xFFFFFFFF))
    return cand.reshape(F * pend_b.shape[0], K1)


def _closure(C, pending, grouped, same, rank, T_flat, bitmat,
             word_idx, shift, n_cols, canon: bool,
             blk_size: int = _BLOCK, pack_bits: int = 0):
    """Fixpoint of fire-expansion ∪ dedup — covers every linearization
    order of any subset of pending ops (the union is monotone, so the
    unique count is stationary exactly at the fixpoint). Each pass
    expands the slot axis in ``blk_size``-sized rounds (adaptively the
    WHOLE axis when ``F·W`` fits the candidate budget — the dedup sort
    is the dominant cost, and one sort of ``F·(W+1)`` rows beats
    ``ceil(W/8)`` sorts of ``F·9``), folding every round into the
    running set with a sort — bounded buffers with TRUE capacity
    semantics: overflow is flagged only when the deduplicated config
    count itself exceeds ``F`` (a candidate buffer can never, since a
    round emits at most ``F·blk_size`` rows). Chained fires missed
    inside a pass are caught by the outer fixpoint. Termination
    compares only DEDUPLICATED pass counts with each other — the
    entering set's count may be stale (canonicalization can merge rows
    without re-deduplicating), so it must not seed the comparison."""
    import jax.numpy as jnp
    from jax import lax

    F = C.shape[0]
    W = pending.shape[0]

    def cond(c):
        _, count, prev, overflow = c
        return (count != prev) & ~overflow

    def body(c):
        C, count, _, _ = c
        C2, count2, overflow = C, count, False
        for lo in range(0, W, blk_size):
            cand = _expand_block(C, pending, grouped, same, rank, T_flat,
                                 bitmat, word_idx, shift, n_cols, lo,
                                 canon, blk_size)
            U = jnp.concatenate([C2, cand], axis=0)
            C2, count2 = _sort_unique_compact(U, F, pack_bits)
            overflow = overflow | (count2 > F)
        return C2, count2, count, overflow

    C, count, _, overflow = lax.while_loop(
        cond, body, (C, jnp.int32(-1), jnp.int32(-2), False))
    return C, count, overflow


def _project(C, count, j):
    """Return of the op in (dynamic) slot ``j``: keep configs that
    linearized it, clearing its bit so the slot can be reused. Clearing
    one fixed bit in every surviving row preserves the sorted-unique
    order, so compaction needs no re-sort."""
    import jax.numpy as jnp

    F, K1 = C.shape
    K = K1 - 1
    wi = j >> 5
    bit = jnp.uint32(1) << (j & 31).astype(jnp.uint32)
    valid = C[:, K] != jnp.uint32(0xFFFFFFFF)
    sel = C[:, wi]
    keep = valid & ((sel & bit) != 0)
    C = C.at[:, wi].set(sel & ~bit)
    C = jnp.where(keep[:, None], C, jnp.uint32(0xFFFFFFFF))
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    pos = jnp.where(keep, pos, F)
    out = jnp.full((F, K1), jnp.uint32(0xFFFFFFFF))
    out = out.at[pos].set(C, mode="drop")
    return out, jnp.sum(keep.astype(jnp.int32))


def _walk(T_flat, n_cols, canon, blk_size, pack_bits,
          ret_slot, slot_ops,
          crashed_slot, bitmat, word_idx, shift, C0, count0):
    """Drive one segment of return events over the sparse frontier
    (callers slice the stream into fixed-size segments — bounded device
    programs keep compilations shape-stable and give the host abort/retry
    points between calls). Returns ``(r, C, count, status)``: status 1 =
    the frontier emptied at segment-local return ``r`` (violation
    witness), 2 = capacity overflow at return ``r``. On a non-running
    exit ``C``/``count`` are the frontier AT ENTRY of return ``r`` (one
    [F, K+1] select per return keeps them), so an overflow resumes
    EXACTLY at the failing return in a 4× buffer — no segment replay."""
    import jax.numpy as jnp
    from jax import lax

    Rn = ret_slot.shape[0]

    def cond(c):
        r, _, _, status = c
        return (r < Rn) & (status == _STATUS_RUNNING)

    def body(c):
        r, C, count, _ = c
        j = ret_slot[r]

        def do(C, count):
            ops_row = slot_ops[r]
            if canon:
                grouped, same, rank = _slot_groups(ops_row, crashed_slot[r])
                Cc = _canonicalize(C, grouped, same, rank, word_idx, shift,
                                   bitmat)
            else:
                grouped = same = rank = None
                Cc = C
            C1, count1, overflow = _closure(
                Cc, ops_row, grouped, same, rank, T_flat, bitmat,
                word_idx, shift, n_cols, canon, blk_size, pack_bits)
            C2, count2 = _project(C1, count1, j)
            status = jnp.where(
                overflow, _STATUS_OVERFLOW,
                jnp.where(count2 == 0, _STATUS_DEAD, _STATUS_RUNNING))
            return C2, count2, status

        def pad(C, count):
            return C, count, jnp.int32(_STATUS_RUNNING)

        C2, count2, status = lax.cond(j >= 0, do, pad, C, count)
        keep = status == _STATUS_RUNNING
        C = jnp.where(keep, C2, C)
        count = jnp.where(keep, count2, count)
        r = jnp.where(keep, r + 1, r)
        return r, C, count, status

    return lax.while_loop(
        cond, body, (jnp.int32(0), C0, count0,
                     jnp.int32(_STATUS_RUNNING)))


@functools.lru_cache(maxsize=None)
def _jitted_walk():
    import jax
    return jax.jit(_walk, static_argnums=(1, 2, 3, 4))


# -- host driver -------------------------------------------------------------

def _slot_geometry(W: int):
    K = (W + 31) // 32
    w = np.arange(W, dtype=np.int32)
    word_idx = w >> 5
    shift = w & 31
    bitmat = np.zeros((W, K), np.uint32)
    bitmat[w, word_idx] = np.uint32(1) << shift
    return K, word_idx, shift, bitmat


def _initial_frontier(F: int, K: int, initial_state: int) -> np.ndarray:
    C0 = np.full((F, K + 1), 0xFFFFFFFF, np.uint32)
    C0[0, :K] = 0
    C0[0, K] = initial_state
    return C0


def _crashed_slots_ref(stream: ev.EventStream, packed: h.PackedHistory,
                       W: int) -> np.ndarray:
    """Readable per-event scan reference for :func:`_crashed_slots`
    (kept as the test oracle)."""
    crashed = np.asarray(packed.crashed, bool)
    n_ret = int(np.sum(stream.kind[:stream.n_events] == ev.KIND_RETURN))
    out = np.zeros((n_ret, W), bool)
    cur = np.full(W, -1, np.int64)
    r = 0
    for e in range(stream.n_events):
        k = stream.kind[e]
        if k == ev.KIND_INVOKE:
            cur[stream.slot[e]] = stream.entry[e]
        elif k == ev.KIND_RETURN:
            active = cur >= 0
            out[r, active] = crashed[cur[active]]
            cur[stream.slot[e]] = -1
            r += 1
    return out


def _crashed_slots(stream: ev.EventStream, packed: h.PackedHistory,
                   W: int) -> np.ndarray:
    """``bool[R, W]`` aligned with :func:`events.returns_view`: whether the
    op pending in slot ``w`` at return ``r`` crashed. Feeds the device-side
    interchangeability grouping (crashed slots sharing an op id).

    Vectorized (O(W·R) numpy, no per-event Python loop): for each slot,
    the occupying entry at a return position is found by a searchsorted
    over that slot's own event positions; the slot is occupied when its
    last event at or before the return is an invoke — or is that very
    return (the returning op is still pending in its snapshot, matching
    ``returns_view``)."""
    crashed = np.asarray(packed.crashed, bool)
    E = stream.n_events
    kind = stream.kind[:E]
    slot = stream.slot[:E]
    entry = stream.entry[:E]
    ret_pos = np.nonzero(kind == ev.KIND_RETURN)[0]
    out = np.zeros((len(ret_pos), W), bool)
    for w in range(W):
        pos_w = np.nonzero(slot == w)[0]
        if len(pos_w) == 0:
            continue
        j = np.searchsorted(pos_w, ret_pos, side="right") - 1
        valid = j >= 0
        jc = np.clip(j, 0, None)
        last = pos_w[jc]
        occupied = valid & ((kind[last] == ev.KIND_INVOKE)
                            | (last == ret_pos))
        out[:, w] = occupied & crashed[entry[last]]
    return out


_SEG = 2048                    # returns per device call: bounded kernels,
                               # one compilation per (W, F), host abort
                               # points. Big segments amortize the host
                               # sync per call (its cost is unmeasured
                               # on the chip); exact-resume escalation
                               # means a large segment costs nothing
                               # extra on overflow.


def _seg_arrays(rs: ev.ReturnStream, crashed_slot: np.ndarray,
                base: int):
    """Static-shape [_SEG] segment slices starting at return ``base``
    (identity-padded past the end) — resume points land on arbitrary
    return indices, so slices are rebuilt host-side per dispatch."""
    W = rs.slot_ops.shape[1]
    ret_slot = np.full(_SEG, -1, np.int32)
    slot_ops = np.full((_SEG, W), -1, np.int32)
    crashed = np.zeros((_SEG, W), bool)
    n = min(_SEG, rs.R - base)
    ret_slot[:n] = rs.ret_slot[base:base + n]
    slot_ops[:n] = rs.slot_ops[base:base + n]
    crashed[:n] = crashed_slot[base:base + n]
    return ret_slot, slot_ops, crashed, n


def _run_walk(memo: Memo, rs: ev.ReturnStream, crashed_slot: np.ndarray,
              F: int, max_frontier: int, should_abort=None):
    """Drive the whole (padded) return stream in ``_SEG``-sized device
    calls, carrying the frontier across segments. On capacity overflow
    the walk resumes EXACTLY at the failing return — the device carries
    the entry frontier of the current return, so the host re-embeds it
    into a 4× buffer and dispatches from that return (no replay).
    Returns ``(dead_ret, status, C, count, F)``; raises
    :class:`FrontierOverflow` past ``max_frontier``."""
    import jax.numpy as jnp

    W = rs.W
    K, word_idx, shift, bitmat = _slot_geometry(W)
    S, O = memo.table.shape
    T_flat = jnp.asarray(memo.table.reshape(-1))
    bitmat_d = jnp.asarray(bitmat)
    word_idx_d = jnp.asarray(word_idx)
    shift_d = jnp.asarray(shift)
    canon = bool(crashed_slot.any())
    # single-key packed dedup when a whole row fits one u32
    pack_bits = W if (K == 1 and S <= (1 << (32 - W)) - 1) else 0
    C = jnp.asarray(_initial_frontier(F, K, memo.initial))
    count = jnp.int32(1)
    walk = _jitted_walk()
    base = 0
    while base < rs.R:
        if should_abort is not None and should_abort():
            return -1, _STATUS_ABORT, C, count, F
        ret_slot, slot_ops, crashed, n = _seg_arrays(rs, crashed_slot,
                                                     base)
        r, C2, count2, status = walk(
            T_flat, O, canon, _round_blk(F, W), pack_bits,
            jnp.asarray(ret_slot), jnp.asarray(slot_ops),
            jnp.asarray(crashed),
            bitmat_d, word_idx_d, shift_d, C, count)
        status = int(status)
        if status == _STATUS_OVERFLOW:
            F *= 4
            if F > max_frontier:
                raise FrontierOverflow(
                    f"reachable config set exceeds {max_frontier} rows")
            # C2 is the frontier at entry of the failing return
            # (sorted-unique rows): sentinel-pad embeds it in the
            # larger buffer
            C = jnp.asarray(np.pad(
                np.asarray(C2), ((0, F - np.asarray(C2).shape[0]), (0, 0)),
                constant_values=np.uint32(0xFFFFFFFF)))
            count = count2
            base += int(r)              # resume at the failing return
            continue
        if status != _STATUS_RUNNING:
            return base + int(r), status, C2, count2, F
        C, count = C2, count2
        base += n
    return rs.R, _STATUS_RUNNING, C, count, F


def _pad_rows(a: np.ndarray, n: int) -> np.ndarray:
    return np.pad(a, ((0, n - len(a)), (0, 0)))


def _final_configs(memo: Memo, rs: ev.ReturnStream,
                   crashed_slot: np.ndarray, F: int, dead_ret: int,
                   limit: int = 16, should_abort=None
                   ) -> List[Dict[str, Any]]:
    """Decode the configurations alive just before the dead return — the
    knossos ``:final-paths`` analogue (same shape as
    :func:`jepsen_tpu.checkers.reach._final_configs`)."""
    prefix = ev.ReturnStream(
        ret_slot=rs.ret_slot[:dead_ret], slot_ops=rs.slot_ops[:dead_ret],
        ret_event=rs.ret_event[:dead_ret], ret_entry=rs.ret_entry[:dead_ret],
        W=rs.W, n_returns=dead_ret)
    R_pad = -(-max(dead_ret, 1) // _SEG) * _SEG
    prefix = ev.pad_returns(prefix, R_pad)
    _dr, status, C, count, _ = _run_walk(
        memo, prefix, _pad_rows(crashed_slot[:dead_ret], R_pad), F, F,
        should_abort=should_abort)
    if status != _STATUS_RUNNING:
        return []                  # aborted mid-evidence: skip the garnish
    C_np = np.asarray(C)
    pending = rs.slot_ops[dead_ret]
    K = (rs.W + 31) // 32
    out = []
    for row in C_np[:min(int(count), limit)]:
        s = int(np.int32(row[K]))
        if s < 0:
            break
        lin = [str(memo.distinct_ops[pending[w]])
               for w in range(rs.W)
               if (row[w >> 5] >> (w & 31)) & 1 and pending[w] >= 0]
        out.append({"model": str(memo.states[s]),
                    "linearized-pending": lin})
    return out


def check(model: Model, history: Sequence[Op], *,
          max_states: int = 100_000, max_slots: int = MAX_SLOTS,
          frontier0: int = 1 << 10, max_frontier: int = 1 << 17,
          time_limit: Optional[float] = None, should_abort=None,
          devices: Optional[Sequence] = None) -> Dict[str, Any]:
    """Check one history with the sparse frontier engine. Raises
    :class:`FrontierOverflow`,
    :class:`~jepsen_tpu.checkers.events.ConcurrencyOverflow` (needs more
    than ``max_slots`` ≤ 128 pending slots), or
    :class:`~jepsen_tpu.models.memo.StateExplosion` — the facade catches
    these and falls back to the CPU searches. Exceeding ``time_limit`` (or
    ``should_abort()`` returning true between device calls) yields
    ``valid == "unknown"``."""
    return check_packed(model, h.pack(history), max_states=max_states,
                        max_slots=max_slots, frontier0=frontier0,
                        max_frontier=max_frontier, time_limit=time_limit,
                        should_abort=should_abort, devices=devices)


def check_packed(model: Model, packed: h.PackedHistory, *,
                 max_states: int = 100_000, max_slots: int = MAX_SLOTS,
                 frontier0: int = 1 << 10, max_frontier: int = 1 << 17,
                 time_limit: Optional[float] = None, should_abort=None,
                 devices: Optional[Sequence] = None) -> Dict[str, Any]:
    t0 = _time.monotonic()
    if packed.n == 0 or packed.n_ok == 0:
        return {"valid": True, "engine": "frontier", "events": 0,
                "time-s": 0.0}
    deadline = t0 + time_limit if time_limit else None

    def aborted():
        if should_abort is not None and should_abort():
            return True
        return deadline is not None and _time.monotonic() > deadline

    max_slots = min(max_slots, MAX_SLOTS)
    memo = reach._cached_memo(model, packed, max_states)
    stream = ev.build(packed, memo, max_slots=max_slots)
    # round-3 fast path: when the crashed-op quotient's PRODUCT space
    # (state × 2^live-slots × Π per-group counts) is enumerable, walk
    # it densely (reach_q) — microseconds per return and one device
    # dispatch, vs the sparse rows' per-return sort/expand. Budget
    # overflows (many live slots, too many distinct crashed groups, or
    # a huge count product) fall through to the sparse walk below.
    if _use_quotient() and (devices is None or len(devices) <= 1):
        try:
            from jepsen_tpu.checkers import reach_q
        except ImportError:                             # degraded install
            obs.count("engine.skipped.frontier-quotient.unavailable")
            obs.decision("frontier-quotient", "skipped",
                         cause="unavailable")
            reach_q = None
        if reach_q is not None:
            try:
                q = reach_q.check_quotient(memo, stream, packed,
                                           should_abort=aborted)
                elapsed = _time.monotonic() - t0
                if q["valid"] is True:
                    out = reach._result_valid("frontier", stream, memo,
                                              elapsed)
                else:
                    out = reach._result_invalid(
                        "frontier", stream, memo, packed,
                        q["dead-event"], elapsed)
                    for k in ("final-configs", "previous-ok"):
                        if k in q:
                            out[k] = q[k]
                out["quotient"] = "dense-product"
                out["product-space"] = q["product-space"]
                return out
            except reach_q.QuotientOverflow:
                # capacity decline (budgeted product space), not a
                # death: recorded route, sparse walk below decides
                obs.decision("frontier-quotient", "route",
                             cause="quotient-overflow")
            # jtlint: ok fallback — abort cause carried in the returned verdict
            except reach_q.Aborted:
                cause = ("timeout" if deadline is not None
                         and _time.monotonic() > deadline else "aborted")
                return {"valid": "unknown", "cause": cause,
                        "engine": "frontier",
                        "time-s": _time.monotonic() - t0}
            except Exception as e:                      # noqa: BLE001
                reach._warn_pallas_failed(f"reach_q: {e!r}")
    rs = ev.returns_view(stream)
    crashed_slot = _crashed_slots(stream, packed, rs.W)
    R_pad = -(-max(rs.n_returns, 1) // _SEG) * _SEG
    # bucket the slot axis (4 sizes per octave) so jit compilations are
    # shared across histories of similar concurrency
    W_pad = min(max(reach._bucket(rs.W, 4), 4), MAX_SLOTS)
    rs = ev.pad_returns(rs, R_pad, W_pad)
    crashed_slot = np.pad(
        _pad_rows(crashed_slot, R_pad),
        ((0, 0), (0, W_pad - crashed_slot.shape[1])))
    F = max(64, frontier0)
    if devices is not None and len(devices) > 1:
        # SURVEY §7 phase 4: frontier + dedup sharded over the mesh —
        # n× capacity, n parallel dedup sorts, all_to_all row routing
        dead_ret, status, _, _, F = _run_walk_sharded(
            memo, rs, crashed_slot, F, max_frontier, devices,
            should_abort=aborted)
    else:
        dead_ret, status, _, _, F = _run_walk(memo, rs, crashed_slot, F,
                                              max_frontier,
                                              should_abort=aborted)
    if status == _STATUS_ABORT:
        cause = ("timeout" if deadline is not None
                 and _time.monotonic() > deadline else "aborted")
        return {"valid": "unknown", "cause": cause, "engine": "frontier",
                "time-s": _time.monotonic() - t0}
    elapsed = _time.monotonic() - t0
    if status == _STATUS_RUNNING:
        out = reach._result_valid("frontier", stream, memo, elapsed)
        out["frontier-cap"] = F
        return out
    out = reach._result_invalid(
        "frontier", stream, memo, packed, int(rs.ret_event[dead_ret]),
        elapsed)
    out["frontier-cap"] = F
    try:
        out["final-configs"] = _final_configs(memo, rs, crashed_slot, F,
                                              dead_ret,
                                              should_abort=aborted)
        if dead_ret > 0:
            prev = packed.entries[int(rs.ret_entry[dead_ret - 1])]
            out["previous-ok"] = prev.op.to_dict()
    # jtlint: ok fallback — witness evidence is best-effort garnish on a decided verdict
    except Exception:                                   # noqa: BLE001
        pass                            # evidence is best-effort garnish
    return out


# -- mesh-sharded walk (SURVEY.md §7 phase 4: frontier + dedup over ICI) -----
#
# The frontier shards across a 1-D device mesh: each device owns the
# config rows whose hash lands on it (owner = row-hash mod n), giving n×
# the capacity and n parallel dedup sorts. Exactness is preserved by
# construction: a config row deterministically belongs to exactly one
# shard, so after hash-routing (lax.all_to_all over ICI) a LOCAL
# sort-unique is a GLOBAL dedup — no cross-shard duplicate can exist.
# Fire candidates route to their owners each closure round; projection
# and canonicalization change row bits (and therefore owners), so rows
# re-route after each. Termination, death, and overflow are psum-reduced
# so every shard takes identical control-flow decisions (SPMD).

_HASH_A = 0x9E3779B1           # golden-ratio odd constants (uint32 wrap)
_HASH_B = 0x85EBCA77


def _hash_rows_np(rows: np.ndarray, n: int) -> np.ndarray:
    """Owner shard of each row (host mirror of :func:`_hash_rows`)."""
    a = np.uint32(_HASH_A)
    h = np.zeros(len(rows), np.uint32)
    for c in range(rows.shape[1]):
        h = (h ^ rows[:, c].astype(np.uint32)) * a
        h = (h >> np.uint32(16)) ^ (h * np.uint32(_HASH_B))
    return (h % np.uint32(n)).astype(np.int32)


def _hash_rows(rows, n: int):
    """Owner shard of each row (device; must match the host mirror)."""
    import jax.numpy as jnp

    a = jnp.uint32(_HASH_A)
    h = jnp.zeros(rows.shape[0], jnp.uint32)
    for c in range(rows.shape[1]):
        h = (h ^ rows[:, c]) * a
        h = (h >> jnp.uint32(16)) ^ (h * jnp.uint32(_HASH_B))
    return (h % jnp.uint32(n)).astype(jnp.int32)


def _bucket_by_owner(rows, n_dev: int, cap: int):
    """Scatter rows into ``n_dev`` destination buckets of ``cap`` rows
    (invalid-filled). Returns ``(send: u32[n_dev, cap, K1], dropped)``
    where ``dropped`` is true when some bucket overflowed ``cap``."""
    import jax.numpy as jnp

    N, K1 = rows.shape
    valid = rows[:, K1 - 1] != jnp.uint32(0xFFFFFFFF)
    owner = jnp.where(valid, _hash_rows(rows, n_dev), n_dev)
    bufs = []
    dropped = False
    for d in range(n_dev):
        mask = owner == d
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        pos = jnp.where(mask & (pos < cap), pos, cap)
        buf = jnp.full((cap, K1), jnp.uint32(0xFFFFFFFF))
        bufs.append(buf.at[pos].set(rows, mode="drop"))
        dropped = dropped | (jnp.sum(mask.astype(jnp.int32)) > cap)
    return jnp.stack(bufs), dropped


def _route_rows(rows, n_dev: int, cap: int, axis: str):
    """Exchange rows so each lands on its owner shard: bucket by owner,
    ``all_to_all`` over the mesh, flatten. Returns
    ``(recv: u32[n_dev*cap, K1], dropped)``."""
    from jax import lax

    send, dropped = _bucket_by_owner(rows, n_dev, cap)
    recv = lax.all_to_all(send, axis, 0, 0, tiled=False)
    return recv.reshape(n_dev * cap, rows.shape[1]), dropped


def _closure_sharded(C, pending, grouped, same, rank, T_flat,
                     bitmat, word_idx, shift, n_cols, canon: bool,
                     n_dev: int, axis: str):
    """Sharded fixpoint: expand locally in ``_BLOCK``-slot rounds, route
    every round's candidates to their owner shards, fold into the local
    set with a sort-unique (globally deduplicating, by the ownership
    invariant). The fixpoint test and overflow flag are psum-global, and
    — as in :func:`_closure` — compare only deduplicated pass counts."""
    import jax.numpy as jnp
    from jax import lax

    F_l = C.shape[0]
    W = pending.shape[0]
    # per-destination routing depth: a round emits up to F_l·_BLOCK
    # candidate rows (duplicates included, counted on the send side), so
    # small meshes need deeper buckets than uniform hashing alone
    # suggests; skew beyond the cap just flags overflow (sound: the host
    # escalates)
    cap = max(F_l, F_l * _BLOCK // n_dev)

    def cond(c):
        _, gcount, prev, overflow = c
        return (gcount != prev) & ~overflow

    def body(c):
        C, gcount, _, _ = c
        C2, lcount2, overflow = C, jnp.int32(0), False
        for lo in range(0, W, _BLOCK):
            cand = _expand_block(C, pending, grouped, same, rank, T_flat,
                                 bitmat, word_idx, shift, n_cols, lo,
                                 canon)
            recv, dropped = _route_rows(cand, n_dev, cap, axis)
            U = jnp.concatenate([C2, recv], axis=0)
            C2, lcount2 = _sort_unique_compact(U, F_l)
            overflow = overflow | (lcount2 > F_l) | dropped
        gcount2 = lax.psum(lcount2, axis)
        goverflow = lax.psum(overflow.astype(jnp.int32), axis) > 0
        return C2, gcount2, gcount, goverflow

    C, gcount, _, overflow = lax.while_loop(
        cond, body, (C, jnp.int32(-1), jnp.int32(-2), False))
    return C, gcount, overflow


def _reroute_full(C, n_dev: int, axis: str):
    """Re-establish the ownership invariant after rows changed bits
    (canonicalize / projection): route all local rows, then local
    sort-unique (which also merges configs that canonicalization made
    equal). Send buckets are F_l-deep, so sends never drop."""
    import jax.numpy as jnp

    F_l = C.shape[0]
    recv, _ = _route_rows(C, n_dev, F_l, axis)
    return _sort_unique_compact(recv, F_l)


def _walk_sharded(n_cols, canon, n_dev, axis, T_flat, ret_slot, slot_ops,
                  crashed_slot, bitmat, word_idx, shift, C0, count0):
    """Per-shard body of the sharded segment walk (run under
    ``shard_map``); mirrors :func:`_walk` with psum-global liveness."""
    import jax.numpy as jnp
    from jax import lax

    Rn = ret_slot.shape[0]
    F_l = C0.shape[0]

    def cond(c):
        r, _, _, status = c
        return (r < Rn) & (status == _STATUS_RUNNING)

    def body(c):
        r, C, gcount, _ = c
        j = ret_slot[r]

        def do(C, gcount):
            ops_row = slot_ops[r]
            overflow0 = False
            if canon:
                grouped, same, rank = _slot_groups(ops_row, crashed_slot[r])
                C = _canonicalize(C, grouped, same, rank, word_idx, shift,
                                  bitmat)
                C, lcount = _reroute_full(C, n_dev, axis)
                overflow0 = lcount > F_l
            else:
                grouped = same = rank = None
            C1, gcount1, overflow1 = _closure_sharded(
                C, ops_row, grouped, same, rank, T_flat, bitmat,
                word_idx, shift, n_cols, canon, n_dev, axis)
            C2, lcount2 = _project(C1, gcount1, j)
            C2, lcount2b = _reroute_full(C2, n_dev, axis)
            gcount2 = lax.psum(lcount2b, axis)
            goverflow = lax.psum(
                (overflow0 | overflow1 | (lcount2b > F_l))
                .astype(jnp.int32), axis) > 0
            status = jnp.where(
                goverflow, _STATUS_OVERFLOW,
                jnp.where(gcount2 == 0, _STATUS_DEAD, _STATUS_RUNNING))
            return C2, gcount2, status

        def pad(C, gcount):
            return C, gcount, jnp.int32(_STATUS_RUNNING)

        C, gcount, status = lax.cond(j >= 0, do, pad, C, gcount)
        r = jnp.where(status == _STATUS_RUNNING, r + 1, r)
        return r, C, gcount, status

    return lax.while_loop(
        cond, body, (jnp.int32(0), C0, count0,
                     jnp.int32(_STATUS_RUNNING)))


@functools.lru_cache(maxsize=None)
def _jitted_walk_sharded(mesh_devs: tuple, axis: str):
    import jax
    from jax.sharding import PartitionSpec as P

    from jepsen_tpu import parallel as par

    m = par.mesh(axis, list(mesh_devs))
    n_dev = len(mesh_devs)

    def run(T_flat, n_cols, canon, ret_slot, slot_ops, crashed_slot,
            bitmat, word_idx, shift, C0, count0):
        body = functools.partial(_walk_sharded, n_cols, canon, n_dev, axis)
        # check=False: the walk's while_loop mixes replicated and
        # sharded carries, which the static replication checker cannot
        # type on either jax generation (0.4 has no replication rule
        # for `while` at all)
        sm = par.shard_map(
            body, m,
            in_specs=(P(), P(), P(), P(), P(), P(), P(), P(axis), P()),
            out_specs=(P(), P(axis), P(), P()), check=False)
        return sm(T_flat, ret_slot, slot_ops, crashed_slot, bitmat,
                  word_idx, shift, C0, count0)

    return jax.jit(run, static_argnums=(1, 2))


def _initial_frontier_sharded(F_l: int, K: int, initial_state: int,
                              n_dev: int) -> np.ndarray:
    """Global ``u32[n_dev*F_l, K+1]`` with the initial config placed on
    its owner shard (host hash must match the device hash)."""
    C0 = np.full((n_dev * F_l, K + 1), 0xFFFFFFFF, np.uint32)
    row = np.zeros((1, K + 1), np.uint32)
    row[0, K] = initial_state
    owner = int(_hash_rows_np(row, n_dev)[0])
    C0[owner * F_l] = row[0]
    return C0


def _run_walk_sharded(memo: Memo, rs: ev.ReturnStream,
                      crashed_slot: np.ndarray, F: int, max_frontier: int,
                      devices: Sequence, should_abort=None):
    """Sharded analogue of :func:`_run_walk`: ``F`` is the TOTAL frontier
    capacity, split evenly over ``devices``. Escalation re-embeds the
    carried global frontier (host re-hash) into 4× buffers."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jepsen_tpu import parallel as par

    n_dev = len(devices)
    axis = "shards"
    W = rs.W
    K, word_idx, shift, bitmat = _slot_geometry(W)
    S, O = memo.table.shape
    F_l = max(64, -(-F // n_dev))
    walk = _jitted_walk_sharded(tuple(devices), axis)
    m = par.mesh(axis, list(devices))
    sharded = NamedSharding(m, P(axis))
    T_flat = jnp.asarray(memo.table.reshape(-1))
    bitmat_d, word_idx_d, shift_d = (jnp.asarray(bitmat),
                                     jnp.asarray(word_idx),
                                     jnp.asarray(shift))
    canon = bool(crashed_slot.any())
    C = jax.device_put(
        _initial_frontier_sharded(F_l, K, memo.initial, n_dev), sharded)
    count = jnp.int32(1)
    base = 0
    while base < rs.R:
        if should_abort is not None and should_abort():
            return -1, _STATUS_ABORT, C, count, n_dev * F_l
        sl = slice(base, base + _SEG)
        r, C2, count2, status = walk(
            T_flat, O, canon, jnp.asarray(rs.ret_slot[sl]),
            jnp.asarray(rs.slot_ops[sl]), jnp.asarray(crashed_slot[sl]),
            bitmat_d, word_idx_d, shift_d, C, count)
        status = int(status)
        if status == _STATUS_OVERFLOW:
            # re-embed: collect live rows, re-hash onto bigger shards
            # (keep growing until the most-loaded shard fits too). The
            # fetch must go through _fetch: in a multi-process run C
            # spans non-addressable devices (process_allgather there)
            from jepsen_tpu.checkers.reach import _fetch
            rows = _fetch(C)
            rows = rows[rows[:, K] != np.uint32(0xFFFFFFFF)]
            owners = _hash_rows_np(rows, n_dev)
            load = np.bincount(owners, minlength=n_dev).max() if len(rows) \
                else 0
            F_l *= 4
            while F_l < load:
                F_l *= 4
            # the caller's total cap bounds escalation directly; only the
            # INITIAL allocation may exceed a tiny cap, via the
            # unavoidable n_dev*64 per-shard minimum buffer
            if n_dev * F_l > max_frontier:
                raise FrontierOverflow(
                    f"reachable config set exceeds {max_frontier} rows")
            C_np = np.full((n_dev * F_l, K + 1), 0xFFFFFFFF, np.uint32)
            for d in range(n_dev):
                mine = rows[owners == d]
                C_np[d * F_l:d * F_l + len(mine)] = mine
            C = jax.device_put(C_np, sharded)
            continue
        if status != _STATUS_RUNNING:
            return base + int(r), status, C2, count2, n_dev * F_l
        C, count = C2, count2
        base += _SEG
    return rs.R, _STATUS_RUNNING, C, count, n_dev * F_l
