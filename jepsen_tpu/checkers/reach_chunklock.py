"""Chunk-lockstep engine: the lockstep batch kernel's per-return
amortization applied to ONE history.

The single-history returns walk (:mod:`.reach_lane`) is a sequential
chain of tiny matmuls — issue-latency bound at ~0.7-0.8 µs/return with
the MXU nearly idle, while the lockstep batch kernel
(:mod:`.reach_batch`) demonstrates 48-73 ns per history-return when H
independent lane blocks advance together. This module closes that gap
for a single history by making the lane blocks be CHUNKS of one return
stream, walked simultaneously:

1. **Bound pass** (phase A): chunk c's boundary reachable set ``v_c``
   is over-approximated by walking the last ``L`` returns of chunk c-1
   from the FULL config set ⊤. The walk is monotone (a superset input
   yields a superset at every step), so ``v̂_c = F_suffix(⊤) ⊇
   F_suffix(F_prefix(v_0)) = v_c`` — a sound bound costing ``L``
   lockstep steps total (all suffixes advance together through the
   existing batch kernel), instead of the full-depth sequential
   forward pass ``check_chunked`` pays. Projections contract ⊤
   quickly (each return kills the configs that never fired it), so
   the bound is tight in practice — boundary bases on the cas-100k
   history have median ~4 configs.
2. **Seed glue** (XLA, on device): each ``v̂_c``'s configs are ranked
   (cumsum) and dealt round-robin into ``E_pad`` seed groups. When
   ``|v̂_c| <= E_pad`` every seed is a single config; otherwise seeds
   are unions — still sound, because the walk is LINEAR over the
   boolean semiring (``F(A ∪ B) = F(A) ∪ F(B)``), so a union seed's
   image is the union of its members' images.
3. **Restricted transfer pass** (phase B): the same lockstep batch
   kernel — parametrized by its row count, so it is literally
   :func:`reach_batch._batch_call` with ``M := E_pad*M`` — walks every
   chunk's full return stream once, one lane block per chunk, rows
   ``e*M + m`` carrying seed e's evolving config set. One kernel,
   ``ceil(Rn/C)`` lockstep steps.
4. **Fold** (XLA, on device): ``v_{c+1} = ∪ {image[c,e] : seed e
   intersects v_c}``, C tiny steps. Exact whenever every selected
   seed is CONTAINED in ``v_c`` (always true for singleton seeds,
   since ``v_c ⊆ v̂_c``); otherwise the fold is an over-approximation
   and the chunk is flagged ``inexact``. Death of the over-approx
   fold still soundly implies death of the exact walk.

Phases A→glue→B→fold chain as asynchronous device dispatches — the
host syncs ONCE, on the fold's packed output (the engine is shaped
around exactly one device round trip; what that round trip costs on
the chip is unmeasured). The happy path (no
inexact flags) is decided entirely by that fetch; flagged chunks are
rescued host-side by re-walking them sequentially from the exact
boundary set (one lane-kernel dispatch each, rare), and deaths are
localized the same way — identical verdicts and dead indices to the
sequential walk.

Upstream analogue: none — knossos walks one history sequentially on
one core (``knossos/src/knossos/linear.clj``, SURVEY.md §2.2); this is
the TPU answer to its single-history latency wall, and the engine
behind the cas-100k and 10M-op benchmark rungs. Reference behavior
reproduced: knossos.wgl verdict semantics (SURVEY.md §2.2, §3.2).
"""
from __future__ import annotations

import contextlib
import functools
import os
import time as _time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu.checkers import dispatch_core
from jepsen_tpu.checkers import transfer
from jepsen_tpu.checkers.reach_lane import _BLOCK, _FAST_PASSES, _idx_dtype

# default chunk count: C*S lanes must stay within the batch kernel's
# proven geometry (G scratch is [2, C*S, W*C*S] — quadratic in lanes).
# Phase B's issued work grows ~linearly with C (block-diagonal fire)
# while its sequential depth shrinks as 1/C, so the best C falls as
# histories grow and the walk turns compute-bound: measured on the
# cas ladder, C=32 at 100k (0.10 s, round-trip-bound) and C=16 at 10M
# (1.43 s vs 2.34 s at C=32, 1.54 s at C=8). The C=64 geometry fails
# TPU compilation (tpu_compile_helper exit 1) and is never picked.
_CHUNKS = 32
_CHUNKS_LONG = 16
_LONG_RETURNS = 1 << 20

# seed groups per chunk. Phase B's issued work scales linearly with
# e_pad (the config rows are [e_pad*M, C*S]), so the default is
# adaptive: 8 singleton-ish seeds below _EPAD_SMALL returns (the e2e
# there is round-trip-bound anyway, and finer seeds avoid rescues when
# the bound is slightly loose), ONE union seed per chunk above it —
# measured exact (zero rescues) on benchmark histories because the
# suffix bound contracts to the true boundary set, and 8x cheaper at
# the 10M rung where phase B is compute-bound.
_E_PAD = 8
_EPAD_SMALL = 1 << 18

# suffix length for the bound pass: long enough for projections to
# contract ⊤ to (nearly) the true boundary set, short enough that the
# pass is ~free next to phase B. Long walks (e_pad=1: ANY looseness
# flags a rescue, and a rescue re-walks 1/C of millions of returns)
# double it — phase A is a few hundred lockstep steps either way.
_SUFFIX = 256
_SUFFIX_LONG = 512

# engine floor: below this many returns the single-dispatch lane walk
# is already round-trip-bound and chunking buys nothing
MIN_RETURNS = 32768


class ChunklockUnfit(RuntimeError):
    """Geometry outside this engine's envelope; callers fall back."""


def _auto_chunks(S: int, Rn: int) -> int:
    c = _CHUNKS_LONG if Rn >= _LONG_RETURNS else _CHUNKS
    while c > 8 and c * S > 512:
        c //= 2
    return c


def admits(S: int, M: int, W: int, Rn: int) -> bool:
    """Single source of truth for the router's gate: would the engine,
    with the SAME adaptive geometry :func:`walk_chunklock` derives
    (auto chunks, the adaptive ``e_pad`` rule), accept this history?
    Keeps :func:`reach.check_packed`'s pre-check from drifting against
    the engine's own ChunklockUnfit checks."""
    if W > _FAST_PASSES or Rn < MIN_RETURNS:
        return False
    c = max(2, min(_auto_chunks(S, Rn), Rn))
    e = _E_PAD if Rn < _EPAD_SMALL else 1
    return fits(S, M, W, c, e)


# VMEM budget for the phase-B geometry. Deliberately its own constant
# (NOT reach._PALLAS_MAX_VMEM_BYTES, which gates a different kernel's
# P-resident envelope): the C=32/e_pad=8 headline geometry needs
# ~7 MB with headroom, and C=64 fails TPU compilation regardless.
_VMEM_BUDGET = 10 << 20


def fits(S: int, M: int, W: int, C: int, e_pad: int) -> bool:
    """VMEM envelope of the phase-B geometry: the block-diagonal G
    scratch [2, C*S, W*C*S] plus the row-expanded config set
    [e_pad*M, C*S] (bf16/f32 = 2/4 B/elem)."""
    hs = C * S
    g = 2 * hs * W * hs
    r = 3 * e_pad * M * hs
    bytes_per = 2 if hs >= 128 else 4   # bf16 gate (reach_batch)
    return (g + r) * bytes_per <= _VMEM_BUDGET


@functools.cache
def _glue_call(C: int, M: int, S: int, e_pad: int):
    """Jitted seed extraction: phase A's final sets → per-chunk seed
    masks [C, e_pad, M*S], the phase-B initial rows [e_pad*M, C*S],
    and per-chunk bound sizes."""
    import jax
    import jax.numpy as jnp

    MS = M * S

    def glue(final_a):
        va = final_a.reshape(M, C, S) > 0.5
        flat = va.transpose(1, 0, 2).reshape(C, MS)         # [C, MS]
        cnt = flat.sum(axis=1).astype(jnp.int32)
        rank = jnp.cumsum(flat.astype(jnp.int32), axis=1) - flat
        grp = rank % e_pad
        seeds = flat[:, None, :] & (
            grp[:, None, :] == jnp.arange(e_pad)[None, :, None])
        r0b = seeds.reshape(C, e_pad, M, S).transpose(1, 2, 0, 3)
        return (seeds.astype(jnp.float32),
                r0b.reshape(e_pad * M, C * S).astype(jnp.float32),
                cnt)

    return jax.jit(glue)


@functools.cache
def _fold_call(C: int, M: int, S: int, e_pad: int):
    """Jitted on-device fold over the restricted transfer images.
    Output is ONE packed f32 array (a single fetch decides the happy
    path): row 0 = [dead_chunk, inexact[0..C), count[0..C)], rows
    1..C+1 = the boundary sets v_0..v_C."""
    import jax
    import jax.numpy as jnp

    MS = M * S
    HW = max(MS, 1 + 2 * C)     # packed row width: head must fit

    def fold(final_b, seeds, cnt):
        images = (final_b.reshape(e_pad, M, C, S) > 0.5)
        images = images.transpose(2, 0, 1, 3).reshape(
            C, e_pad, MS).astype(jnp.float32)
        v0 = jnp.zeros(MS, jnp.float32).at[0].set(1.0)
        all_v = jnp.zeros((C + 1, MS), jnp.float32).at[0].set(v0)

        def step(c, carry):
            v, dead, inexact, all_v = carry
            sc = jax.lax.dynamic_index_in_dim(seeds, c, 0, False)
            ic = jax.lax.dynamic_index_in_dim(images, c, 0, False)
            active = (sc @ v > 0.5).astype(jnp.float32)     # [e_pad]
            sel = active @ sc                               # [MS]
            bad = jnp.any((sel > 0.5) & (v < 0.5))
            inexact = inexact.at[c].set(bad)
            vn = (active @ ic > 0.5).astype(jnp.float32)
            dead = jnp.where((dead < 0) & ~jnp.any(vn > 0.5),
                             c, dead)
            all_v = all_v.at[c + 1].set(vn)
            return vn, dead, inexact, all_v

        _, dead, inexact, all_v = jax.lax.fori_loop(
            0, C, step, (v0, jnp.int32(-1),
                         jnp.zeros(C, jnp.bool_), all_v))
        head = jnp.zeros(HW, jnp.float32)
        head = head.at[0].set(dead.astype(jnp.float32))
        head = head.at[1:1 + C].set(inexact.astype(jnp.float32))
        head = head.at[1 + C:1 + 2 * C].set(cnt.astype(jnp.float32))
        if HW > MS:
            all_v = jnp.pad(all_v, ((0, 0), (0, HW - MS)))
        return jnp.concatenate([head[None], all_v], axis=0)

    return jax.jit(fold)


def _chunk_operands(ret_slot: np.ndarray, slot_ops: np.ndarray,
                    C: int, per: int, per_pad: int, L: int, L_pad: int,
                    idx_dt) -> Tuple[np.ndarray, ...]:
    """Marshal the return stream into the two lockstep layouts: phase A
    rows = per-boundary suffixes (front-padded with identity rows —
    harmless from ⊤), phase B rows = the chunks themselves."""
    Rn = int(ret_slot.shape[0])
    W = int(slot_ops.shape[1])
    rs_a = np.full((L_pad, C), -1, np.int8)
    ops_a = np.full((L_pad, C, W), -1, idx_dt)
    for c in range(1, C):
        end = min(c * per, Rn)
        lo = max(0, end - L)
        n = end - lo
        if n > 0:
            rs_a[L_pad - n:, c] = ret_slot[lo:end]
            ops_a[L_pad - n:, c] = slot_ops[lo:end]
    rs_b = np.full((per_pad, C), -1, np.int8)
    ops_b = np.full((per_pad, C, W), -1, idx_dt)
    for c in range(C):
        lo, hi = c * per, min((c + 1) * per, Rn)
        if hi > lo:
            rs_b[:hi - lo, c] = ret_slot[lo:hi]
            ops_b[:hi - lo, c] = slot_ops[lo:hi]
    return rs_a, ops_a, rs_b, ops_b


def _localize(P: np.ndarray, ret_slot: np.ndarray,
              slot_ops: np.ndarray, M: int, v_entry: np.ndarray,
              c: int, per: int, interpret: bool
              ) -> Tuple[int, Optional[np.ndarray]]:
    """Sequentially re-walk chunk ``c`` from its exact boundary set:
    returns ``(global_dead_or_-1, exit_set_or_None)``."""
    from jepsen_tpu.checkers import reach_lane

    Rn = int(ret_slot.shape[0])
    S = P.shape[1]
    lo, hi = c * per, min((c + 1) * per, Rn)
    r0_sm = v_entry.reshape(M, S).T
    dead, r_final = reach_lane.walk_returns(
        P, ret_slot[lo:hi], slot_ops[lo:hi], r0_sm,
        interpret=interpret)
    if dead >= 0:
        return lo + dead, None
    return -1, np.asarray(r_final).T.reshape(M * S)


def _seed(v_entry: np.ndarray, c: int, per: int, M: int
          ) -> Tuple[int, np.ndarray]:
    """Witness seed of a death in chunk ``c``: ``(ret_lo, R_lo)``, the
    chunk's first return and its exact entry set as bool ``[S, M]``
    (the layout :func:`_localize` walks from), for
    :func:`reach._final_configs`'s ``start``."""
    return c * per, v_entry.reshape(M, -1).T


def _host_fold(P: np.ndarray, ret_slot: np.ndarray,
               slot_ops: np.ndarray, M: int, seeds_np: np.ndarray,
               images_np: np.ndarray, v: np.ndarray, start: int,
               C: int, per: int, interpret: bool,
               diag: Dict[str, Any]
               ) -> Tuple[int, Optional[Tuple[int, np.ndarray]]]:
    """Host-side exact fold over the per-chunk seed/image summaries —
    the ONE recovery/combination loop (ISSUE 19) shared by the
    single-process inexact rescue and the multi-host gathered fold.
    Boolean algebra only, so it is bit-identical to the on-device
    :func:`_fold_call` wherever that fold is exact; chunks whose
    selected union seeds escape the exact boundary set are re-walked
    sequentially (:func:`_localize`). Returns the global dead return
    index (-1 = linearizable) and, on a death, the dead chunk's exact
    entry set as a witness seed (:func:`_seed`)."""
    for c in range(start, C):
        active = seeds_np[c] @ v > 0             # [e_pad] selected
        sel = active @ seeds_np[c] > 0
        if not (sel & ~v).any():
            vn = active @ images_np[c] > 0
        else:
            diag["rescues"] += 1
            dead, vn = _localize(P, ret_slot, slot_ops, M, v, c, per,
                                 interpret)
            if dead >= 0:
                return dead, _seed(v, c, per, M)
        if not vn.any():
            dead, _ = _localize(P, ret_slot, slot_ops, M, v, c, per,
                                interpret)
            if dead < 0:
                raise ChunklockUnfit(
                    "fold death not confirmed by re-walk")
            return dead, _seed(v, c, per, M)
        v = vn
    return -1, None


def _walk_dist(shard, P: np.ndarray, ret_slot: np.ndarray,
               slot_ops: np.ndarray, M: int, C: int, e_pad: int,
               suffix: int, per: int, interpret: bool, phase_b,
               seeds_d, cnt_d) -> Tuple[int, Dict[str, Any]]:
    """Multi-host tail of :func:`walk_chunklock`: phase B runs only on
    this process's contiguous shard of the chunk axis, the per-chunk
    images are thresholded and word-packed (PR-12 packing — 32x
    smaller than dense f32 before the packed-wire framing even
    applies), and ONE ``all_gather`` along the DCN axis assembles the
    full summary set; the fold then runs host-side through the same
    :func:`_host_fold` loop as the single-process rescue. A peer that
    dies mid-gather costs availability of its summaries, not
    correctness: the operand slices are replicated on every host, so
    the missing chunks' images are re-derived locally and exactly one
    ``engine.fallback("dist-gather")`` is recorded after the rescue
    succeeds."""
    from jepsen_tpu.checkers import reach_word

    S = int(P.shape[1])
    MS = M * S
    Pn = int(shard.process_count)
    lo, hi = shard.chunk_range(C)
    perc = -(-C // Pn)

    def images_of(fb_dev, n_rows: int) -> np.ndarray:
        fb = np.asarray(fb_dev) > 0.5
        return fb.reshape(e_pad, M, n_rows, S).transpose(2, 0, 1, 3) \
            .reshape(n_rows, e_pad, MS)

    NW = (MS + 31) // 32
    diag: Dict[str, Any] = {"chunks": C, "rescues": 0}
    # pod driver (rank 0 daemon): ship the walk operands FIRST so the
    # compute peers enter the same walk — their phase B overlaps this
    # rank's — and the gather rendezvouses; the driver lock spans
    # send→gather because collectives match by issue order, so two
    # concurrent checks interleaving theirs would cross-wire every
    # rank. SPMD callers (tests, dryrun — every rank already runs this
    # walk) skip the send. A torn pod fails the send or the gather,
    # and the SAME exact-rescue below recovers both.
    from jepsen_tpu.parallel import distributed
    driver = (distributed.driver_mode() and shard.process_index == 0)
    lock = distributed.driver_lock() if driver else \
        contextlib.nullcontext()
    local = None
    t_g = _time.monotonic()
    try:
        with lock:
            if driver:
                distributed.send_work(
                    {"op": "chunklock", "P": P, "ret_slot": ret_slot,
                     "slot_ops": slot_ops, "M": M, "n_chunks": C,
                     "e_pad": e_pad, "suffix": suffix,
                     "interpret": int(interpret)},
                    timeout_s=distributed.gather_timeout_s())
            t_b = _time.monotonic()
            local = images_of(phase_b(lo, hi), hi - lo) if hi > lo \
                else np.zeros((0, e_pad, MS), bool)
            obs.count("dist.device_s", _time.monotonic() - t_b)
            words = np.zeros((perc * e_pad, NW), np.uint32)
            if hi > lo:                 # pad ranks to a common shape
                words[:(hi - lo) * e_pad] = reach_word.pack_rows(
                    local.reshape((hi - lo) * e_pad, MS))
            gathered = shard.gather(words)      # [Pn, perc*e_pad, NW]
        wall = _time.monotonic() - t_g
        actual = int(gathered.nbytes)
        baseline = gathered.shape[0] * gathered.shape[1] * MS * 4
        transfer.count_collective(actual, baseline)
        obs.count("dist.gather")
        obs.count("dist.dcn_wall_s", wall)
        bits = reach_word.unpack_rows(
            gathered.reshape(Pn * perc * e_pad, -1), MS)
        images_np = bits.reshape(Pn * perc, e_pad, MS)[:C]
        rescued = 0
    except Exception as e:                              # noqa: BLE001
        # exact-rescue: every host holds the FULL operand slices, so
        # the missing chunks' images are re-derived locally; the one
        # fallback record lands only after the re-derivation succeeds
        def rederive() -> np.ndarray:
            full = np.zeros((C, e_pad, MS), bool)
            ranges = [(0, C)]
            if local is not None:
                full[lo:hi] = local
                ranges = [(0, lo), (hi, C)]
            for rlo, rhi in ranges:
                if rhi > rlo:
                    full[rlo:rhi] = images_of(phase_b(rlo, rhi),
                                              rhi - rlo)
            return full

        images_np = dispatch_core.rescue_once(
            "dist-gather", type(e).__name__, rederive)
        rescued = C - (hi - lo)
        obs.count("dist.rescue_chunks", rescued)
    seeds_np = np.asarray(seeds_d) > 0.5         # [C, e_pad, MS]
    counts = np.asarray(cnt_d).astype(np.int64)
    v0 = np.zeros(MS, bool)
    v0[0] = True
    dead, _ = _host_fold(P, ret_slot, slot_ops, M, seeds_np, images_np,
                         v0, 0, C, per, interpret, diag)
    obs.gauge("dist.processes", Pn)
    diag["basis-max"] = int(counts.max(initial=0))
    diag["dist"] = {"processes": Pn, "local_chunks": [int(lo), int(hi)],
                    "rescued_chunks": rescued}
    if not rescued:
        diag["dist"].update({
            "dcn_bytes": actual, "dcn_bytes_unpacked": baseline,
            "dcn_ratio": round(baseline / max(actual, 1), 2),
            "gather_wall_s": round(wall, 6)})
    return dead, diag


class ChunklockInflight:
    """A launched-but-unfetched chunk-lockstep walk: phases A/glue/B
    and the fold are all queued on device, the ONE round trip (the
    fold's packed verdict words) has not crossed the wire yet.
    Produced by :func:`launch_chunklock`, consumed by
    :func:`collect_chunklock` — the split lets a pipelined caller walk
    the NEXT history's chunks while this one's fold drains.  The
    multi-host shard path is inherently synchronous (the DCN gather IS
    the fetch), so there ``result`` is already materialized and
    ``collect`` just hands it back, with no witness seed."""

    __slots__ = ("packed", "final_b", "seeds_d", "P", "ret_slot",
                 "slot_ops", "M", "C", "e_pad", "per", "interpret",
                 "result")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))

    def ready(self) -> bool:
        """True when the fold's verdict words can be fetched without
        blocking (conservative: unknown means ready)."""
        if self.result is not None:
            return True
        return dispatch_core.poll_ready(self.packed)


def launch_chunklock(P: np.ndarray, ret_slot: np.ndarray,
                     slot_ops: np.ndarray, M: int, *,
                     n_chunks: Optional[int] = None,
                     e_pad: Optional[int] = None,
                     suffix: Optional[int] = None,
                     interpret: bool = False,
                     shard: Optional[Any] = None
                     ) -> "ChunklockInflight":
    """Stage half of the chunk-lockstep walk: dispatch phases A, glue,
    B (through the batch engine's double-buffered segment pipeline)
    and the fold, returning a :class:`ChunklockInflight` WITHOUT
    fetching the verdict words.  :func:`walk_chunklock` is the
    blocking composition.

    ``shard`` (a :class:`jepsen_tpu.parallel.distributed.ChunkShard`,
    default auto-detected from the ``jax.distributed`` runtime) engages
    the multi-host variant: phases A/glue are replicated (cheap and
    deterministic, so every process derives identical seeds), phase B
    walks only the local chunk range, and the word-packed summaries
    cross DCN once (:func:`_walk_dist`). Pass ``shard=False`` to force
    the single-process path inside a distributed runtime (the
    differential tests' reference)."""
    import jax.numpy as jnp

    from jepsen_tpu.checkers import reach_batch

    O1, S, _ = P.shape
    Rn = int(ret_slot.shape[0])
    W = int(slot_ops.shape[1])
    if W > _FAST_PASSES:
        raise ChunklockUnfit(f"W={W} beyond exact-ladder cap")
    if e_pad is None:
        e_pad = _E_PAD if Rn < _EPAD_SMALL else 1
    if suffix is None:
        suffix = _SUFFIX if Rn < _EPAD_SMALL else _SUFFIX_LONG
    C = n_chunks if n_chunks is not None else _auto_chunks(S, Rn)
    C = max(2, min(C, Rn))
    if not fits(S, M, W, C, e_pad):
        raise ChunklockUnfit("geometry exceeds VMEM envelope")
    if shard is None:
        if dist_enabled():
            from jepsen_tpu.parallel import distributed
            shard = distributed.ChunkShard.detect()
    elif shard is False:
        shard = None
    per = -(-Rn // C)
    blk = min(32, _BLOCK) if interpret else \
        min(_BLOCK, reach_batch._adaptive_block(C, W))
    per_pad = -(-per // blk) * blk
    L = max(1, min(suffix, per))
    b_a = min(blk, L)
    L_pad = -(-L // b_a) * b_a
    idx_dt = _idx_dtype(O1)
    rs_a, ops_a, rs_b, ops_b = _chunk_operands(
        ret_slot, slot_ops, C, per, per_pad, L, L_pad, idx_dt)
    # phase A seeds: block 0 walks nothing from the exact one-hot v_0
    # (its "bound" is the true initial set); blocks 1.. walk their
    # suffix from ⊤
    r0_a = np.ones((M, C * S), np.float32)
    r0_a[:, :S] = 0.0
    r0_a[0, 0] = 1.0
    P32 = np.ascontiguousarray(P, np.float32)
    cdt = reach_batch._COMPUTE_DTYPE if C * S >= 128 else "float32"
    n_pass = W                      # exact closure — both phases need
    run_a = reach_batch._batch_call(  # soundness, not an under-approx
        b_a, W, M, S, C, O1, L_pad, n_pass, interpret, cdt)
    # phase-A seeds are 0/1 exactly: they cross the wire bit-packed
    # (8 per byte, unpacked on device by _batch_call.run) through the
    # shared dispatch core — a packed dispatch failure records one
    # fallback and retries dense
    a_base = (ops_a.size * 4 + rs_a.size * 4 + P32.nbytes
              + r0_a.nbytes)
    _ck_a, final_a = dispatch_core.dispatch_packed(
        run_a, (ops_a.reshape(-1), rs_a, P32), r0_a, a_base)
    seeds_d, r0_b, cnt_d = _glue_call(C, M, S, e_pad)(final_a)

    def phase_b(lo: int, hi: int):
        """Phase B over chunks [lo, hi) — the ONE lockstep dispatch
        the single-process fold and every shard of the multi-host
        path run, through the batch engine's segmented put+dispatch
        pipeline (segment i+1's operand upload streams while the
        device walks segment i, no intermediate fetch)."""
        Cl = hi - lo
        if lo == 0 and hi == C:
            args_b = (ops_b.reshape(-1), rs_b, P32, r0_b)
        else:
            r0_np = np.ascontiguousarray(
                np.asarray(r0_b).reshape(e_pad * M, C, S)[:, lo:hi]
                .reshape(e_pad * M, Cl * S))
            args_b = (np.ascontiguousarray(
                          ops_b[:, lo:hi]).reshape(-1),
                      np.ascontiguousarray(rs_b[:, lo:hi]), P32,
                      r0_np)
        geom_b = (blk, W, e_pad * M, S, Cl, O1, per_pad)
        _cks, final_b = reach_batch._pipe_walk_b(
            args_b, geom_b, n_pass, interpret, {})
        return final_b

    if shard is not None and getattr(shard, "process_count", 1) > 1:
        res = _walk_dist(shard, P, ret_slot, slot_ops, M, C, e_pad,
                         suffix, per, interpret, phase_b, seeds_d,
                         cnt_d)
        return ChunklockInflight(result=res)
    final_b = phase_b(0, C)
    packed = _fold_call(C, M, S, e_pad)(final_b, seeds_d, cnt_d)
    return ChunklockInflight(
        packed=packed, final_b=final_b, seeds_d=seeds_d, P=P,
        ret_slot=ret_slot, slot_ops=slot_ops, M=M, C=C, e_pad=e_pad,
        per=per, interpret=interpret)


def collect_chunklock(inf: "ChunklockInflight"
                      ) -> Tuple[int, Dict[str, Any],
                                 Optional[Tuple[int, np.ndarray]]]:
    """Collect half: fetch the fold's packed verdict words (the ONE
    round trip) and run the verdict / localize / host-refold tail.
    Bit-identical to the pre-split walk — the split moves only WHEN
    the fetch blocks, never what is fetched. Returns ``(dead, diag,
    seed)``: ``seed`` is a death's witness seed (:func:`_seed`; None
    when valid, and on the multi-host path, whose witness decodes from
    the initial state)."""
    if inf.result is not None:
        return (*inf.result, None)
    P, ret_slot, slot_ops = inf.P, inf.ret_slot, inf.slot_ops
    M, C, e_pad, per = inf.M, inf.C, inf.e_pad, inf.per
    interpret, final_b, seeds_d = inf.interpret, inf.final_b, \
        inf.seeds_d
    S = int(P.shape[1])
    out = np.asarray(inf.packed)                 # the ONE round trip
    MS = M * S
    dead_chunk = int(out[0, 0])
    inexact = out[0, 1:1 + C] > 0.5
    counts = out[0, 1 + C:1 + 2 * C].astype(np.int64)
    all_v = out[1:, :MS] > 0.5                   # [C+1, MS]
    diag = {"chunks": C, "basis-max": int(counts.max(initial=0)),
            "rescues": 0}
    last = C if dead_chunk < 0 else dead_chunk
    if not inexact[:last].any():
        # fold exact up to the deciding chunk
        if dead_chunk < 0:
            return -1, diag, None
        # death under an exact (or chunk-local over-approx) entry set
        # is a true death — localize the exact return inside the chunk
        dead, _ = _localize(P, ret_slot, slot_ops, M,
                            all_v[dead_chunk], dead_chunk, per,
                            interpret)
        if dead < 0:        # defensive: fold/walk disagreement
            raise ChunklockUnfit("fold death not confirmed by re-walk")
        return dead, diag, _seed(all_v[dead_chunk], dead_chunk, per, M)
    # rescue path: refold host-side from the first flagged chunk,
    # re-walking any chunk whose selected union seeds escape the exact
    # boundary set (only overflow chunks — |v̂| > e_pad — can flag)
    seeds_np = np.asarray(seeds_d) > 0.5         # [C, e_pad, MS]
    fb = np.asarray(final_b) > 0.5
    images_np = fb.reshape(e_pad, M, C, S).transpose(2, 0, 1, 3) \
        .reshape(C, e_pad, MS)
    start = int(np.nonzero(inexact)[0][0])
    dead, seed = _host_fold(P, ret_slot, slot_ops, M, seeds_np,
                            images_np, all_v[start], start, C, per,
                            interpret, diag)
    return dead, diag, seed


def walk_chunklock(P: np.ndarray, ret_slot: np.ndarray,
                   slot_ops: np.ndarray, M: int, *,
                   n_chunks: Optional[int] = None,
                   e_pad: Optional[int] = None,
                   suffix: Optional[int] = None,
                   interpret: bool = False,
                   shard: Optional[Any] = None
                   ) -> Tuple[int, Dict[str, Any]]:
    """Chunk-lockstep returns walk over one history (blocking
    composition of :func:`launch_chunklock` and
    :func:`collect_chunklock`). Returns ``(dead, diag)``: ``dead`` is
    the first return index at which the exact config set emptied
    (-1 = linearizable), bit-identical to
    :func:`reach_lane.walk_returns`; ``diag`` carries chunk geometry
    and rescue counts."""
    return walk_seeded(P, ret_slot, slot_ops, M, n_chunks=n_chunks,
                       e_pad=e_pad, suffix=suffix, interpret=interpret,
                       shard=shard)[:2]


def walk_seeded(P: np.ndarray, ret_slot: np.ndarray,
                slot_ops: np.ndarray, M: int, **kw
                ) -> Tuple[int, Dict[str, Any],
                           Optional[Tuple[int, np.ndarray]]]:
    """:func:`walk_chunklock` (same keywords) returning
    ``(dead, diag, seed)``: ``seed`` is the dead chunk's exact entry
    set for :func:`attach_witness` (None when valid or multi-host).
    ``diag`` stays free of arrays."""
    return collect_chunklock(launch_chunklock(P, ret_slot, slot_ops, M,
                                              **kw))


def attach_witness(out: Dict[str, Any], memo, rs, P_np: np.ndarray,
                   S_pad: int, M: int, W: int, dead: int, packed,
                   seed: Optional[Tuple[int, np.ndarray]]) -> None:
    """The chunk-lockstep route's witness decode: walk only the dead
    chunk's returns before ``dead``, from the entry set ``seed`` the
    walk handed back; without a seed, the whole prefix from the
    initial state. Either way the same ``final-configs``."""
    from jepsen_tpu.checkers import reach

    lo = 0 if seed is None else int(seed[0])
    with obs.span("reach.witness", engine="reach-chunklock",
                  walked=int(dead) - lo):
        if seed is None:
            obs.count("witness.unseeded")
        else:
            obs.count("witness.seeded")
        reach._attach_witness(out, memo, rs, P_np, S_pad, M, W, dead,
                              packed, start=seed)


def check_packed(model, packed, *, max_states: int = 100_000,
                 max_slots: int = 20, max_dense: int = 1 << 22,
                 n_chunks: Optional[int] = None,
                 e_pad: Optional[int] = None,
                 suffix: Optional[int] = None,
                 interpret: bool = False,
                 process_shard: Optional[Any] = None) -> Dict[str, Any]:
    """Standalone entry (the ``chunklock`` algorithm name): prep +
    chunk-lockstep walk + knossos-style verdict/witness. Raises
    :class:`ChunklockUnfit` / :class:`reach.DenseOverflow` etc. when
    the history is outside the envelope — callers fall back.
    ``process_shard`` forwards to :func:`walk_chunklock`'s ``shard``
    (None = auto-detect the multi-host runtime, False = force
    single-process, or an injected ChunkShard)."""
    from jepsen_tpu.checkers import events as ev
    from jepsen_tpu.checkers import reach

    t0 = _time.monotonic()
    if packed.n == 0 or packed.n_ok == 0:
        return {"valid": True, "engine": "reach-chunklock",
                "events": 0, "time-s": 0.0}
    memo, stream, _T, S_pad, M = reach._prep(
        model, packed, max_states=max_states, max_slots=max_slots,
        max_dense=max_dense)
    W = max(stream.W, 1)
    if not reach._fast_ok(S_pad, W, M, memo.n_ops):
        raise ChunklockUnfit("outside fast-path budget")
    rs = ev.returns_view(stream)
    if rs.n_returns < 2:
        raise ChunklockUnfit("too few returns")
    P_np = reach._build_P(memo, S_pad)
    dead, diag, seed = walk_seeded(
        P_np, rs.ret_slot, rs.slot_ops, M, n_chunks=n_chunks,
        e_pad=e_pad, suffix=suffix, interpret=interpret,
        shard=process_shard)
    elapsed = _time.monotonic() - t0
    if dead < 0:
        out = reach._result_valid("reach-chunklock", stream, memo,
                                  elapsed)
    else:
        out = reach._result_invalid("reach-chunklock", stream, memo,
                                    packed, int(rs.ret_event[dead]),
                                    elapsed)
        attach_witness(out, memo, rs, P_np, S_pad, M, W, int(dead),
                       packed, seed)
    out.update(diag)
    return out


def enabled() -> bool:
    return not os.environ.get("JEPSEN_TPU_NO_CHUNKLOCK")


def dist_enabled() -> bool:
    """Gate on the multi-host chunk-axis sharding (auto-detected from
    the ``jax.distributed`` runtime when on)."""
    return not os.environ.get("JEPSEN_TPU_NO_DIST_CHUNKLOCK")
