"""Lockstep-batched lane kernel: H independent histories advance
through the dense-reachability returns walk TOGETHER, one return index
per step, with their config sets side by side along the lane axis.

Why: the single-history walk (``reach_lane``) is a sequential chain of
tiny [M,S]@[S,W*S] matmuls — per-ISSUE latency bound, with the MXU and
VPU almost idle (MFU ~0.04%). Checking a BATCH of histories one after
another pays that latency wall H times. Lockstep batching pays it once:

- config sets live as ONE array ``R [M, H*S]`` (history h owns lane
  block ``h*S:(h+1)*S``);
- the per-return fire matmul becomes ONE ``[M, H*S] @ [H*S, W*H*S]``
  issue against a BLOCK-DIAGONAL transition operand (history h's
  pending ops in rows ``h*S:(h+1)*S``, slot-major column blocks), so
  the off-diagonal zero blocks guarantee no cross-history terms and
  the MXU amortizes one issue over H histories;
- every VPU op (fire blends, projection) operates on ``[M, H*S]``
  lanes — H× the lane utilization of the single-history kernel;
- the pending-count gate ladder (see ``reach_lane._ladder_fire``) is
  gated by ``max_h c_r(h)`` — ≥ each history's own bound, so the walk
  stays EXACT per history (extra passes past a history's fixpoint are
  idempotent).

Projection is per-history (different slots return at the same step, or
none: identity): a pre-expanded per-return lane row ``jv [H*S]``
(lane block h holds ``ret_slot_h`` as f32) turns the W static
projections + identity into W+1 batched blend terms with lane-wise
0/1 indicator multiplies — the same blend trick as the single kernel,
vectorized across the batch.

Death detection mirrors the lane kernel: per-block checkpoints of the
whole batched set, host-side per-history localization, and an exact
single-history block re-walk (``reach_lane._refine_dead``) only for
histories that died. Histories are independent throughout — verdicts
and dead indices are bit-identical to running the single-history walk
H times (differentially tested in ``tests/test_reach_batch.py``).

Upstream analogue: none — knossos checks one history per JVM run; this
is the TPU-native answer to "a Jepsen run produced several large
histories" (e.g. ``test-count > 1`` or per-node sub-histories), and
the engine behind the ``cas-100k x 8`` benchmark rung. Reference
behavior being reproduced: knossos.wgl per-history semantics
(SURVEY.md §2.2).
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu.checkers import dispatch_core, transfer
from jepsen_tpu.checkers.reach_lane import (_BLOCK, _FAST_PASSES,
                                            _idx_dtype, _refine_dead)

# segments for the put+dispatch pipeline (one fetch; transfers of
# segment i+1 stream while the device walks segment i). The batch
# operand set is H× the single-history one, so it pipelines finer:
# interleaved ablation on 32 × cas-100k measured 8 segments ~8-10%
# faster e2e than the single-history path's 4 (1.54/1.61 vs 1.67/1.78
# best/median), while 12 gave it back on per-dispatch overhead; the
# single-history walk is nseg-neutral (453 KB of operands, measured
# medians equal) and keeps its own 4.
_PIPE_NSEG = 8

# default for the ``interpret`` flag of the marshal/dispatch entry
# points when the caller passes None: tests flip this to route EVERY
# dispatch — including the streaming prep pipeline's, whose scheduler
# never threads an interpret argument — through interpret mode on CPU
_INTERPRET_DEFAULT = False

# SMEM byte budget for the double-buffered slot_ops window
# (B*H*W i32 ×2 buffers). The chip holds 1 MB of SMEM: the H=32,
# B=1024 geometry needed 1.31 MB and failed to compile while 0.655 MB
# fit (BASELINE.md round-4 batch rung) — so the block size shrinks as
# the lockstep width grows instead of capping H at 16.
_SMEM_BUDGET = 840_000


def _adaptive_block(H: int, W: int) -> int:
    """Largest power-of-two block ≤ ``_BLOCK`` whose double-buffered
    slot_ops SMEM window fits the measured budget. B=1024 up to H=16
    at W=5 (the round-4 default geometry), B=512 at H=32, B=256 at
    H=64 — the window stays ~655 KB at every width."""
    cap = max(32, _SMEM_BUDGET // (H * W * 8))
    b = 1 << (cap.bit_length() - 1)
    return min(_BLOCK, b)


# Mosaic's default scoped-VMEM limit (v5e), what a kernel that asks
# for no more gets; and what the wide-M kernel may ask for of the 128
# MiB of VMEM a v5e core has
_SCOPED_VMEM = 16 << 20
_VMEM_CAP = 96 << 20
# transition rows the VMEM budget allows for (a 5-value CAS register's
# union alphabet is 37 with the sentinel, a 7-value one 64)
_O1_BUDGET = 64
# rows of the config set the wide-M kernel touches per inner step
_TILE_ROWS = 512
# f32 temporaries of one tile the wide-M kernel's body keeps live
_TILE_TEMPS = 8


class Geometry(NamedTuple):
    """Which lockstep kernel a group of one mask width walks, and how
    many histories it walks together: ``wide`` for the wide-M kernel
    (``_make_wide_kernel``), else the one-array kernel, at ``H``
    lanes. The dispatch derives the rest from the group's shape: the
    block from :func:`group_geom`, the dtype from the lane width, the
    wide-M kernel's scoped-VMEM limit from :func:`_wide_vmem_limit`."""
    H: int
    wide: bool


def _pad(x: int, m: int) -> int:
    return -(-int(x) // m) * m


def vmem_bytes(W: int, S: int, H: int, B: int, wide: bool,
               O1: int = _O1_BUDGET) -> int:
    """VMEM the batch kernel holds at this geometry, buffer by buffer,
    each padded to the (8 sublane, 128 lane) tile (a lane axis narrower
    than 128 still takes 128 lanes).

    Both kernels: the double-buffered ``jv`` block ``(B, HS)`` f32 and
    transition rows ``(O1, S, S)`` f32, and the ``(2, HS, W*HS)``
    transition scratch in the compute dtype. The one-array kernel
    (``_make_batch_kernel``) adds the double-buffered ``(M, HS)`` seed
    (compute dtype), checkpoint and final (f32) blocks, the ``(M, HS)``
    config-set scratch, the fire matmul's ``(M, W*HS)`` f32 image and
    two ``(M, HS)`` f32 blend temporaries. The wide-M kernel keeps the
    seed, checkpoints and final in HBM and adds only its two ``(M,
    HS)`` config-set buffers and ``_TILE_TEMPS`` f32 temporaries of one
    ``_TILE_ROWS``-row tile. Calibrated on AOT compiles for a v5e at
    S=8: the one-array kernel at H=32 fits the default limit up to W=9
    and is refused at W=10, at H=16 fits at W=10 and is refused at
    W=11 (``tests/test_chip_compile.py``)."""
    M = 1 << W
    HS = H * S
    lanes = _pad(HS, 128)
    c = 2 if (HS >= 128 and _COMPUTE_DTYPE == "bfloat16") else 4
    common = (2 * _pad(B, 8) * lanes * 4
              + 2 * O1 * _pad(S, 8) * _pad(S, 128) * 4
              + 2 * _pad(HS, 16) * _pad(W * HS, 128) * c)
    if wide:
        T = min(M, _TILE_ROWS)
        return (common + 2 * M * lanes * c
                + _TILE_TEMPS * _pad(T, 8) * lanes * 4)
    return (common + 2 * M * lanes * c + 4 * M * lanes * 4
            + M * lanes * c + M * _pad(W * HS, 128) * 4
            + 2 * M * lanes * 4)


def lockstep_geometry(W: int, S: int) -> Optional[Geometry]:
    """The one place a lockstep group's kernel and lanes are chosen
    from its mask width ``W`` and state count ``S``: 32 histories (the
    keyed lanes' group, ``reach._BATCH_GROUP``) on the one-array kernel
    where, at the SMEM-budgeted block, it fits Mosaic's default scoped
    VMEM (W <= 9 at S = 8: every group of the fault-free keyed batch);
    else the wide-M kernel at the narrowest full lane tile (``128 //
    S`` histories: 16 at S = 8), which asks for a raised scoped limit
    of twice its counted buffers; None where that limit passes
    ``_VMEM_CAP`` (no group of that width can be built)."""
    lanes = 32
    if vmem_bytes(W, S, lanes, _adaptive_block(lanes, max(W, 1)),
                  False) <= _SCOPED_VMEM:
        return Geometry(lanes, False)
    H = max(1, 128 // S)
    if _wide_vmem_limit(W, S, H, _adaptive_block(H, max(W, 1))) \
            > _VMEM_CAP:
        return None
    return Geometry(H, True)


def _wide_vmem_limit(W: int, S: int, H: int, B: int,
                     O1: int = _O1_BUDGET) -> int:
    """The scoped VMEM the wide-M kernel asks for: twice its counted
    buffers (room for what the compiler adds), never below the
    default."""
    return max(_SCOPED_VMEM, 2 * vmem_bytes(W, S, H, B, True, O1))


def plan_buckets(R_lens, W: int, *, group: int = 32, key_W=None,
                 S: int = 8) -> List[List[int]]:
    """Length-bucketed lane packing: partition a ragged batch of return
    streams into lockstep dispatch groups such that no stream walks
    more than ~2x its own padded length. Streams are assigned to
    power-of-two length buckets and each bucket is split (longest
    first) into the fewest groups of at most ``group`` lanes, as even
    as can be — so a 10k-return history no longer forces 200-return
    co-batched keys to walk 10k padded lockstep steps, and a bucket's
    groups, sizes within one of each other, pad to one lane width and
    so share one compiled geometry (a short tail group would compile
    its own, and its own rescue walk only in the batch whose tail holds
    a dying key).

    Lengths at or below the dispatch block size (the SMEM-budgeted
    ``_adaptive_block`` floor, which every group pads to anyway) share
    ONE floor bucket: splitting them buys nothing and costs extra
    dispatches + compile geometries. (The floor uses the production
    block size; interpret-mode dispatches use a smaller block, making
    the floor bucket merely coarser there — suboptimal packing, never
    incorrect.) Groups are ordered longest bucket first so the
    pipelined scheduler overlaps later (cheaper) groups' marshalling
    and compiles with the big walk.

    With per-key mask widths ``key_W`` (and the batch's state count
    ``S``) keys are first split by mask cost: every key whose width
    fits the one-array kernel (:func:`lockstep_geometry`) shares one
    class, planned as above with the width hint ``W``; each wider
    width is a class of its own, in groups of its geometry's lanes, so
    a key with crashed ops never sets the mask width of its crash-free
    neighbours. Wide classes come first, widest first (the costliest
    walks). Returns a partition of ``range(len(R_lens))`` — every
    index appears in exactly one group."""
    widths = [0] * len(R_lens) if key_W is None else \
        [int(w) for w in key_W]
    geos = {w: lockstep_geometry(w, S) for w in set(widths) if w}
    classes: dict = {}
    for i, w in enumerate(widths):
        wide = w and geos[w] is not None and geos[w].wide
        classes.setdefault(w if wide else 0, []).append(i)
    groups: List[List[int]] = []
    for c in sorted(classes, reverse=True):
        idxs = classes[c]
        size = geos[c].H if c else group
        floor = _adaptive_block(max(1, min(size, len(idxs))),
                                max(c or W, 1))
        order = sorted(idxs, key=lambda i: (-int(R_lens[i]), i))
        buckets: dict = {}
        for i in order:
            eff = max(int(R_lens[i]), floor, 1)
            buckets.setdefault((eff - 1).bit_length(), []).append(i)
        for key in sorted(buckets, reverse=True):
            b = buckets[key]
            k = -(-len(b) // size)
            groups.extend(b[j * len(b) // k:(j + 1) * len(b) // k]
                          for j in range(k))
    return groups


def group_widths(groups, key_W, S: int) -> List[int]:
    """The mask width each planned group walks at: a wide class's own
    W, and for the class the one-array kernel holds (see
    :func:`plan_buckets`) the widest W of any key in that class — so
    its groups share one geometry whichever keys a rotated batch puts
    together, and no batch compiles a geometry the one before it did
    not."""
    geos = {int(w): lockstep_geometry(int(w), S) for w in set(key_W)}
    narrow = [int(w) for w in key_W
              if geos[int(w)] is not None and not geos[int(w)].wide]
    top = max(narrow, default=1)
    out = []
    for g in groups:
        w = max(int(key_W[k]) for k in g)
        out.append(top if w <= top else w)
    return out


def mesh_lockstep_enabled() -> bool:
    """The device-sharded lockstep lane (lane blocks placed across a
    mesh's devices) is on by default wherever a mesh is supplied;
    ``JEPSEN_TPU_NO_MESH_LOCKSTEP=1`` forces the pre-mesh routes
    (consulted per call — tests toggle it)."""
    return not os.environ.get("JEPSEN_TPU_NO_MESH_LOCKSTEP")


def shard_groups_for_mesh(groups: List[List[int]], n_dev: int
                          ) -> Tuple[List[List[int]], int]:
    """Lane-axis sharding at the planner level: split dispatch groups
    into per-device lane blocks until at least ``n_dev`` groups exist,
    so a batch that packs into fewer groups than the mesh has devices
    still walks on every chip. The widest group splits first, into two
    equal halves — its lane count padded to even by REPLICATING its
    first lane, so both halves share one compiled geometry and the pad
    lane's verdict write is idempotent (it walks the same stream as
    the lane it copies). Returns ``(groups, pad_lanes)``; every input
    index still appears in some group, single-lane groups cannot
    split, so a tiny batch may underfill the mesh."""
    out = [list(g) for g in groups]
    pad = 0
    while len(out) < n_dev:
        widest = max(range(len(out)), key=lambda i: len(out[i]))
        g = out.pop(widest)
        if len(g) < 2:
            out.insert(widest, g)
            break
        if len(g) % 2:
            g = g + [g[0]]
            pad += 1
        half = len(g) // 2
        out[widest:widest] = [g[:half], g[half:]]
    return out, pad


def group_geom(R_max: int, H: int, W: int, *,
               interpret: bool = False) -> Tuple[int, int]:
    """Dispatch block size and padded lockstep step count for a group
    of ``H`` streams whose longest member has ``R_max`` returns — the
    ONE source of the padding formula, shared by
    :func:`pack_batch_operands`, the ``tools/batch_width.py`` ragged
    sweep, and the geometry-bounds tests (a formula drift there would
    otherwise silently misreport pack efficiency)."""
    from jepsen_tpu.checkers.reach import _bucket

    B = min(32, _BLOCK) if interpret else _adaptive_block(H, W)
    R_pad = max(B, _bucket(-(-max(int(R_max), 1) // B) * B, B))
    return B, R_pad


def group_diag(geom, R_lens, key_W=None) -> dict:
    """Per-group geometry + pack-efficiency accounting for one lockstep
    dispatch (bench.py's batch rung): real vs padded returns under this
    group's ``(H, B, W, S, M, R_pad)`` geometry; the config rows
    walked (``rows`` = H * M * R_pad); and, over the real lanes' own
    returns, the rows at the group's mask width (``mask_rows`` = M *
    sum of R_key) against those at each key's own (``own_rows`` = sum
    of 2**W_key * R_key over ``key_W``, the lanes' mask widths; the
    group's W where unknown)."""
    B, W, M, S, H, O1, R_pad = geom
    real = int(sum(int(r) for r in R_lens))
    kw = [W] * len(R_lens) if key_W is None else key_W
    own = sum((1 << int(w)) * int(r) for w, r in zip(kw, R_lens))
    return {"H": H, "B": B, "W": W, "M": M, "S": S, "R_pad": R_pad,
            "real_returns": real, "padded_returns": H * R_pad,
            "rows": H * M * R_pad, "mask_rows": M * real,
            "own_rows": own}


def kernel_cache_info() -> dict:
    """Hit/miss counters of the per-geometry compiled-kernel cache
    (:func:`_batch_call`, keyed on ``(B, W, M, S, H, O1, segment,
    passes, dtype)``): a bucketed ragged batch reuses one compiled
    program per distinct geometry, and the bench batch rung surfaces
    these so a geometry-churn regression is visible."""
    ci = _batch_call.cache_info()
    return {"hits": int(ci.hits), "misses": int(ci.misses),
            "entries": int(ci.currsize)}


def _one_fire_pass_b(R, G_all, W: int, M: int, HS: int):
    """One Jacobi fire pass over the batched set: ONE fused
    ``[M,HS] @ [HS, W*HS]`` matmul (block-diagonal G ⇒ history h's
    image depends only on history h's set), then the per-slot mask
    blends on the M axis — identical math to
    ``reach_pallas._one_fire_pass`` with S widened to H*S lanes.
    Exact in bf16 too: operands are 0/1 (exactly representable), the
    dot accumulates in f32 (``preferred_element_type`` — sums can
    reach P's per-column fan-in, so this is load-bearing), and the
    blend compares > 0.5 on the f32 image before any rounding back."""
    import jax.numpy as jnp

    F = jnp.dot(R, G_all, preferred_element_type=jnp.float32)
    for jj in range(W):
        Fj = F[:, jj * HS:(jj + 1) * HS]
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, HS)
        Fr = Fj.reshape(half, 2, blk, HS)
        hi = jnp.maximum(
            Rr[:, 1], (Fr[:, 0] > 0.5).astype(R.dtype))
        R = jnp.stack([Rr[:, 0], hi], axis=1).reshape(M, HS)
    return R


def _ladder_fire_b(R_scr, R, pend_c, G_all, n_pass: int, W: int,
                   M: int, HS: int):
    """Gate-ladder closure on the batched set, gated by the batch-max
    pending count (exact per history: extra passes are idempotent)."""
    from jax.experimental import pallas as pl

    R = _one_fire_pass_b(R, G_all, W, M, HS)
    if n_pass <= 1:
        return R
    R_scr[:] = R
    for off in range(1, n_pass):
        def _deep():
            Rd = R_scr[:]
            R_scr[:] = _one_fire_pass_b(Rd, G_all, W, M, HS)
        pl.when(pend_c > off)(_deep)
    return R_scr[:]


def _gather_G_b(slot_ops_ref, P_ref, k: int, W: int, H: int, S: int,
                O1: int, G_scr, buf):
    """Write return ``k``'s H*W pending-op transition tiles onto the
    diagonal blocks of ``G_scr[buf]`` (slot-major column blocks; the
    off-diagonal blocks were zeroed once at step 0 and are never
    written, preserving history independence). Slot -1 → the all-zero
    sentinel row of P."""
    import jax.numpy as jnp

    HS = H * S
    for hh in range(H):
        for jj in range(W):
            o = slot_ops_ref[(k * H + hh) * W + jj]
            o = jnp.where(o < 0, O1 - 1, o)
            G_scr[buf, hh * S:(hh + 1) * S,
                  jj * HS + hh * S:jj * HS + (hh + 1) * S] = P_ref[o]


def _make_batch_kernel(B: int, W: int, M: int, S: int, H: int,
                       O1: int, n_blocks: int, n_pass: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    HS = H * S

    def kernel(slot_ops_ref, pendmax_ref, jv_ref, P_ref, R0_ref,
               ckpt_ref, final_ref, R_scr, G_scr):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            R_scr[:] = R0_ref[:]
            # zero once: diagonal blocks are overwritten per return,
            # off-diagonal blocks stay zero forever (the independence
            # guarantee of the batched fire matmul)
            G_scr[:] = jnp.zeros_like(G_scr)

        # checkpoints/final stay f32 regardless of the compute dtype
        # (host-side localization reads them with > 0.5 unchanged)
        ckpt_ref[0] = R_scr[:].astype(jnp.float32)  # set at block START
        _gather_G_b(slot_ops_ref, P_ref, 0, W, H, S, O1, G_scr, 0)

        def one(k, R):
            G_all = G_scr[k % 2]
            # prefetch the NEXT return's operand while this return's
            # MXU chain is in flight (G does not depend on R)
            kn = jnp.minimum(k + 1, B - 1)
            _gather_G_b(slot_ops_ref, P_ref, kn, W, H, S, O1, G_scr,
                        (k + 1) % 2)
            R = _ladder_fire_b(R_scr, R, pendmax_ref[k], G_all, n_pass,
                               W, M, HS)
            # per-history projection blend: lane row jv holds each
            # history's returning slot (-1 = none) replicated over its
            # S lanes
            row = jv_ref[k]                      # [HS] f32
            acc = R * (row < 0).astype(R.dtype)
            for jj in range(W):
                half, blk = M >> (jj + 1), 1 << jj
                Rr = R.reshape(half, 2, blk, HS)
                taken = Rr[:, 1]
                proj = jnp.stack([taken, jnp.zeros_like(taken)],
                                 axis=1).reshape(M, HS)
                acc = acc + proj * (row == jj).astype(R.dtype)
            return acc

        def do_return(i, _):
            R_scr[:] = one(i, R_scr[:])
            return 0

        jax.lax.fori_loop(0, B, do_return, 0)

        @pl.when(step == n_blocks - 1)
        def _finish():
            final_ref[:] = R_scr[:].astype(jnp.float32)

    return kernel


def _make_wide_kernel(B: int, W: int, M: int, S: int, H: int, O1: int,
                      n_blocks: int, n_pass: int, T: int):
    """The batch walk for a config set too large for the one-array
    kernel's VMEM (``vmem_bytes``): the same per-return math, with the
    ``[M, HS]`` set in two VMEM buffers walked ``T`` rows at a time.
    The seed, the block checkpoints and the final set stay in HBM and
    cross by DMA, in the compute dtype.

    A fire pass updates the set in place, slot by slot: slot ``jj``
    ORs the transition image of every mask with bit ``jj`` clear into
    the mask ``2**jj`` rows further on (within a tile below ``T``
    rows, between tiles above). A slot fired later in the pass sees
    what earlier slots added, so a pass reaches at least the one-array
    kernel's Jacobi pass and never past the closure: with ``n_pass``
    at least the pending count both walks end on the same closure, and
    a capped ladder stays sound for "valid", as there. The projection
    writes the other buffer. Passes loop ``min(pendmax, n_pass)``
    times, and a step at which no lane has an op pending (a padded
    return) is skipped, its transition tiles never gathered."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    HS = H * S
    n_tiles = M // T
    n_pairs = M // (2 * T)

    def rows(start):
        return pl.ds(pl.multiple_of(start, T), T)

    def pair_start(i, b: int):
        """Start row of the ``i``-th tile whose masks have bit ``b``
        clear (``b`` a multiple of ``T``)."""
        nb = b // T
        return ((i // nb) * 2 * nb + i % nb) * T

    def kernel(slot_ops_ref, pendmax_ref, jv_ref, P_ref, R0_hbm,
               ckpt_hbm, final_hbm, R_scr, G_scr, cur_ref):
        step = pl.program_id(0)
        cdt = R_scr.dtype

        @pl.when(step == 0)
        def _init():
            pltpu.sync_copy(R0_hbm, R_scr.at[0])
            cur_ref[0] = 0
            G_scr[:] = jnp.zeros_like(G_scr)

        pltpu.sync_copy(R_scr.at[cur_ref[0]], ckpt_hbm.at[step])
        _gather_G_b(slot_ops_ref, P_ref, 0, W, H, S, O1, G_scr, 0)

        def fire(c, g):
            for jj in range(W):
                b = 1 << jj
                Gj = G_scr[g, :, jj * HS:(jj + 1) * HS]
                if b >= T:
                    def across(i, _, b=b, Gj=Gj):
                        lo = pair_start(i, b)
                        img = jnp.dot(R_scr[c, rows(lo), :], Gj,
                                      preferred_element_type=jnp.float32)
                        hi = R_scr[c, rows(lo + b), :]
                        R_scr[c, rows(lo + b), :] = jnp.maximum(
                            hi, (img > 0.5).astype(cdt))
                        return 0
                    jax.lax.fori_loop(0, n_pairs, across, 0)
                else:
                    def within(t, _, b=b, Gj=Gj):
                        x = R_scr[c, rows(t * T), :]
                        f = jnp.dot(x, Gj,
                                    preferred_element_type=jnp.float32)
                        half = T // (2 * b)
                        xr = x.reshape(half, 2, b, HS)
                        fr = f.reshape(half, 2, b, HS)
                        hi = jnp.maximum(xr[:, 1],
                                         (fr[:, 0] > 0.5).astype(cdt))
                        R_scr[c, rows(t * T), :] = jnp.stack(
                            [xr[:, 0], hi], axis=1).reshape(T, HS)
                        return 0
                    jax.lax.fori_loop(0, n_tiles, within, 0)

        def project(c, d, row):
            # as the one-array kernel: lane block h keeps the masks
            # that fired its returning slot, bit cleared (-1: identity)
            none = (row < 0).astype(cdt)
            ind = [(row == jj).astype(cdt) for jj in range(W)]

            def within(t, _):
                x = R_scr[c, rows(t * T), :]
                acc = x * none
                for jj in range(W):
                    b = 1 << jj
                    if b >= T:
                        break
                    taken = x.reshape(T // (2 * b), 2, b, HS)[:, 1]
                    p = jnp.stack([taken, jnp.zeros_like(taken)],
                                  axis=1).reshape(T, HS)
                    acc = acc + p * ind[jj]
                R_scr[d, rows(t * T), :] = acc
                return 0
            jax.lax.fori_loop(0, n_tiles, within, 0)
            for jj in range(W):
                b = 1 << jj
                if b < T:
                    continue

                def across(i, _, b=b, on=ind[jj]):
                    lo = pair_start(i, b)
                    R_scr[d, rows(lo), :] = (R_scr[d, rows(lo), :]
                                             + R_scr[c, rows(lo + b), :]
                                             * on)
                    return 0
                jax.lax.fori_loop(0, n_pairs, across, 0)

        def do_return(k, _):
            g = k % 2
            kn = jnp.minimum(k + 1, B - 1)

            @pl.when(pendmax_ref[kn] > 0)
            def _prefetch():
                _gather_G_b(slot_ops_ref, P_ref, kn, W, H, S, O1, G_scr,
                            (k + 1) % 2)
            pend = pendmax_ref[k]

            @pl.when(pend > 0)
            def _walk():
                c = cur_ref[0]

                def one_pass(_p, carry):
                    fire(c, g)
                    return carry
                jax.lax.fori_loop(0, jnp.minimum(pend, n_pass), one_pass,
                                  0)
                project(c, 1 - c, jv_ref[k])
                cur_ref[0] = 1 - c
            return 0

        jax.lax.fori_loop(0, B, do_return, 0)

        @pl.when(step == n_blocks - 1)
        def _finish():
            pltpu.sync_copy(R_scr.at[cur_ref[0]], final_hbm)

    return kernel


# compute dtype for the config sets and transition operand. bf16 is
# EXACT here because every stored value is 0 or 1 (exactly
# representable) and the fire dot ACCUMULATES IN F32 via
# preferred_element_type — column sums can reach the per-column
# fan-in of P (up to S), so the f32 accumulation is the load-bearing
# half of the argument, with the > 0.5 compare reading the f32 image
# before anything is rounded back to bf16. Halves the VMEM footprint
# and traffic of the G operand scratch — the resource that pinned the
# lockstep width at 32 (H=64's f32 geometry exceeded the 16 MB
# scoped-VMEM limit by 212 KB). The one-array kernel's checkpoint/final
# outputs stay f32; the wide-M kernel's cross in the compute dtype, and
# the host reads both as f32.
_COMPUTE_DTYPE = "bfloat16"


@functools.cache
def _batch_call(B: int, W: int, M: int, S: int, H: int, O1: int,
                R_pad: int, n_pass: int, interpret: bool, dtype: str,
                donate: bool = False, tile: int = 0):
    """The jitted batch walk at one geometry: the one-array kernel, or
    with ``tile`` rows (> 0) the wide-M kernel, which asks for the
    scoped VMEM its buffers need (:func:`_wide_vmem_limit`)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    cdt = jnp.dtype(dtype)
    HS = H * S
    n_blocks = R_pad // B
    # 1-D SMEM windows must tile to 1024 (Mosaic layout verification
    # fails on a 512-wide window when the adaptive block shrinks below
    # 1024 at H≥32) — BOTH scalar operands pad each per-grid-step
    # block up to a 1024 multiple on device: pendmax's B-block to PB,
    # and slot_ops' B*H*W-block to SOW_P (B=1024 makes B*H*W a 1024
    # multiple for any H*W, but the adaptive block at H≥32 does not —
    # e.g. a tail group of H=21 at W=5, B=512 is 52.5 tiles). The
    # kernel indexes only the first B*H*W (resp. B) entries of each
    # block, so the tail pad is never read.
    PB = max(B, 1024)
    SOW = B * H * W
    SOW_P = -(-SOW // 1024) * 1024
    scalars = [
        pl.BlockSpec((SOW_P,), lambda i: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec((PB,), lambda i: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec((B, HS), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((O1, S, S), lambda i: (0, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    if tile:
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        call = pl.pallas_call(
            _make_wide_kernel(B, W, M, S, H, O1, n_blocks, n_pass, tile),
            grid=(n_blocks,),
            in_specs=scalars + [hbm],
            out_specs=[hbm, hbm],
            out_shape=[jax.ShapeDtypeStruct((n_blocks, M, HS), cdt),
                       jax.ShapeDtypeStruct((M, HS), cdt)],
            scratch_shapes=[pltpu.VMEM((2, M, HS), cdt),
                            pltpu.VMEM((2, HS, W * HS), cdt),
                            pltpu.SMEM((1,), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=min(_VMEM_CAP,
                                     _wide_vmem_limit(W, S, H, B, O1))),
            interpret=interpret,
        )
    else:
        call = pl.pallas_call(
            _make_batch_kernel(B, W, M, S, H, O1, n_blocks, n_pass),
            grid=(n_blocks,),
            in_specs=scalars + [
                pl.BlockSpec((M, HS), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, M, HS), lambda i: (i, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((M, HS), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_blocks, M, HS), jnp.float32),
                jax.ShapeDtypeStruct((M, HS), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((M, HS), cdt),
                pltpu.VMEM((2, HS, W * HS), cdt),
            ],
            interpret=interpret,
        )

    def run(slot_ops, ret_slot_rh, P, R0):
        # device-side derivations (the wire carries only narrow ints
        # and bit-packed bools): batch-max pending count per return
        # gates the ladder; the projection lane row expands each
        # history's returning slot over its S lanes
        P = P.astype(cdt)
        if R0.dtype == jnp.uint8:
            # bit-packed config seeds (8 per wire byte), unpacked where
            # bandwidth is free
            R0 = jnp.unpackbits(R0, count=M * HS).reshape(M, HS) \
                    .astype(cdt)
        else:
            R0 = R0.astype(cdt)
        if slot_ops.dtype == jnp.uint8:
            # 6-bit packed ops lane (4 values per 3 wire bytes): the
            # dense narrow format is SIGNED, so uint8 unambiguously
            # marks the packed lane
            slot_ops = transfer.unpack_sextet_jnp(slot_ops,
                                                  R_pad * H * W)
        pend = jnp.sum((slot_ops.reshape(-1, H, W) >= 0)
                       .astype(jnp.int32), axis=2)
        pendmax = jnp.max(pend, axis=1)
        ops32 = slot_ops.astype(jnp.int32)
        if PB != B:                     # pad each B-block to the SMEM tile
            pendmax = jnp.pad(pendmax.reshape(-1, B),
                              ((0, 0), (0, PB - B))).reshape(-1)
        if SOW_P != SOW:                # pad each B*H*W-block likewise
            ops32 = jnp.pad(ops32.reshape(-1, SOW),
                            ((0, 0), (0, SOW_P - SOW)),
                            constant_values=-1).reshape(-1)
        jv = jnp.repeat(ret_slot_rh.astype(jnp.float32), S, axis=1)
        return call(ops32, pendmax, jv, P, R0)

    # donated carried config set: XLA recycles the [M, HS] f32 buffer
    # for the segment's `final` output instead of reallocating per
    # dispatch (pipeline-intermediate carries only — see _pipe_walk_b)
    return jax.jit(run, donate_argnums=(3,)) if donate else jax.jit(run)


def _wide_tile(W: int, M: int, S: int) -> int:
    """Rows per tile of the wide-M kernel for a group of mask width
    ``W`` (and ``M`` config rows) at ``S`` states, or 0 where the
    group walks the one-array kernel (:func:`lockstep_geometry`; also
    where no kernel fits, as the host gates never send such a group:
    a direct caller's compile is then refused and takes its
    fallback)."""
    geo = lockstep_geometry(W, S)
    return min(M, _TILE_ROWS) if geo is not None and geo.wide else 0


def _first_passes(W: int, M: int, S: int) -> int:
    """Fire passes of a group's first walk: on the wide-M kernel, whose
    passes loop at run time, every pass a return can need (exact, so a
    key's death needs no rescue walk); on the one-array kernel, whose
    passes unroll, the capped ladder (``_FAST_PASSES``), with an exact
    rescue on a death."""
    return W if _wide_tile(W, M, S) else min(W, _FAST_PASSES)


def pack_batch_operands(P: np.ndarray, ret_slots: List[np.ndarray],
                        slot_ops: List[np.ndarray], M: int, *,
                        interpret: bool = False):
    """Marshal H per-history return streams into the lockstep layout:
    all padded (identity rows: slot -1) to one bucketed ``R_pad``, then
    interleaved return-major — ``slot_ops_flat[(r*H + h)*W + jj]`` and
    ``ret_slot_rh[r, h]`` — so one SMEM/VMEM block holds a contiguous
    run of lockstep steps. Returns ``(geom, host_args, R_lens)``."""
    O1, S, _ = P.shape
    lanes = len(ret_slots)
    W = max(int(so.shape[1]) for so in slot_ops)
    # the wide-M kernel, and the bf16 one-array kernel (whose mask
    # reshapes Mosaic refuses on a lane axis of 28 x 8 = 224), walk
    # whole 128-lane tiles: a group short of one gets pad lanes
    # (identity returns, so each stays alive and is never read)
    H = lanes
    if _wide_tile(W, M, S) or (lanes * S > 128 and lanes * S % 128):
        H = _pad(lanes, max(1, 128 // S))
    R_max = max(1, max(int(r.shape[0]) for r in ret_slots))
    B, R_pad = group_geom(R_max, H, W, interpret=interpret)
    rs_rh = np.full((R_pad, H), -1, np.int8)
    ops_rhw = np.full((R_pad, H, W), -1, np.int32)
    for h in range(lanes):
        n = int(ret_slots[h].shape[0])
        rs_rh[:n, h] = ret_slots[h]
        ops_rhw[:n, h, :slot_ops[h].shape[1]] = slot_ops[h]
    idx_dt = _idx_dtype(O1)
    R0 = np.zeros((M, H * S), np.float32)
    for h in range(H):
        R0[0, h * S] = 1.0                   # mask 0, state 0 per block
    # the per-lane config seeds cross bit-packed (8 configs per wire
    # byte, unpacked on device — see _batch_call.run) unless opted out
    r0_wire = transfer.pack_bool(R0) if transfer.packed_enabled() \
        else R0
    host_args = (np.ascontiguousarray(ops_rhw.reshape(-1), idx_dt),
                 np.ascontiguousarray(rs_rh),
                 np.ascontiguousarray(P, np.float32),
                 r0_wire)
    geom = (B, W, M, S, H, O1, R_pad)
    return geom, host_args, [int(r.shape[0]) for r in ret_slots]


def _pipe_walk_b(host_args, geom, n_pass: int, interpret: bool,
                 dsegs: dict, device=None):
    """Segmented put+dispatch pipeline for the batch walk (same shape
    as ``reach_lane._pipe_walk``): no intermediate fetch, cached device
    segments for rescue reuse. Transfer diet: the transition tensor is
    cached device-resident across the group sequence
    (:func:`transfer.cached_put` — one upload per batch, not per
    group), the config seeds cross bit-packed, and segments after the
    first donate the carried config set so XLA recycles its HBM buffer
    per dispatch. ``device`` (mesh dispatches) keys the operand cache;
    a diet-path failure records exactly one obs fallback and the walk
    degrades to the round-5 dispatch."""
    import jax
    import jax.numpy as jnp

    from jepsen_tpu.checkers.reach_lane import _pipe_geom

    B, W, M, S, H, O1, R_pad = geom
    ops_flat, rs_rh, P, R0 = host_args
    HS = H * S
    seg, nseg = _pipe_geom(B, R_pad, _PIPE_NSEG)
    # bf16 only at full-lane widths: with H*S below the 128-lane tile
    # the bf16 (16,128) tiling degenerates (measured: 8 × cas-100k at
    # HS=64 runs ~2.0 s in bf16 vs 0.47 s in f32, while HS ≥ 128
    # geometries are 6-8% FASTER in bf16)
    cdt = _COMPUTE_DTYPE if HS >= 128 else "float32"
    tile = _wide_tile(W, M, S)
    run = _batch_call(B, W, M, S, H, O1, seg, n_pass, interpret, cdt,
                      False, tile)
    run_d = None
    donate = transfer.donate_enabled()
    sextet = transfer.packed_enabled() and transfer.sextet_ok(O1)
    HW = H * W

    def _seg_host(k: int):
        """Segment ``k``'s host operands in the dense narrow format."""
        lo, hi = k * seg, min((k + 1) * seg, R_pad)
        o_seg = ops_flat[lo * HW:hi * HW]
        r_seg = rs_rh[lo:hi]
        if hi - lo < seg:                # ragged tail: identity pad
            o_seg = np.pad(o_seg, (0, (seg - (hi - lo)) * HW),
                           constant_values=-1)
            r_seg = np.pad(r_seg, ((0, seg - (hi - lo)), (0, 0)),
                           constant_values=-1)
        return (np.ascontiguousarray(o_seg),
                np.ascontiguousarray(r_seg))

    fresh = "segs" not in dsegs
    if fresh:
        # cast to the compute dtype BEFORE the wire: bf16 halves the
        # transfer and the in-jit astype then no-ops (leaving it f32
        # here would re-materialize a converted copy on every segment
        # dispatch)
        dsegs["dP"], p_hit = transfer.cached_put(
            P, (cdt, str(device)), lambda: jnp.asarray(P, dtype=cdt))
        if getattr(R0, "dtype", None) == np.uint8:
            dsegs["dR0"] = jax.device_put(R0)     # bit-packed seeds
        else:
            dsegs["dR0"] = jnp.asarray(R0, dtype=cdt)
        dsegs["segs"] = []
        p_bytes = P.size * (2 if cdt == "bfloat16" else 4)
        # the ops lane crosses 6-bit packed per segment when the
        # alphabet fits the sextet (see the upload loop below)
        ops_wire_b = (nseg * transfer.sextet_bytes(seg * HW)
                      if sextet else int(ops_flat.nbytes))
        # a seed that arrived as a DEVICE array (chunklock phase B
        # hands over _glue_call's output) never crosses the link —
        # count it on neither side of the actual/baseline pair
        r0_host = isinstance(R0, np.ndarray)
        actual = (ops_wire_b + int(rs_rh.nbytes)
                  + (int(dsegs["dR0"].nbytes) if r0_host else 0)
                  + (0 if p_hit else p_bytes))
        baseline = (R_pad * H * W * 4 + R_pad * H * 4 + int(P.nbytes)
                    + (M * HS * 4 if r0_host else 0))
        dsegs["xfer"] = (actual, baseline)
        obs.count("lockstep.transfer_bytes", actual)
        transfer.count_put(actual, baseline)
    R_cur = dsegs["dR0"]
    ckpts = []
    # double-buffered wire: with pipelining on, segment i+1's host pack
    # and device_put are issued BEFORE segment i's dispatch returns
    # control, so the pack/transfer rides under segment i's device walk
    # instead of serializing between launches.  JEPSEN_TPU_NO_PIPELINE
    # restores the strict build-then-dispatch order.
    prefetch = fresh and dispatch_core.pipeline_enabled()

    def _seg_dev(k: int):
        """Segment ``k``'s device operands, built and uploaded on
        first use (cached in ``dsegs`` so rescue re-walks and the
        dense-recover rebuild see prefetched segments identically)."""
        while len(dsegs["segs"]) <= k:
            o_seg, r_seg = _seg_host(len(dsegs["segs"]))
            dsegs["segs"].append(jax.device_put(
                (transfer.pack_sextet(o_seg) if sextet else o_seg,
                 r_seg)))
        return dsegs["segs"][k]

    for i in range(nseg):
        if fresh:
            _seg_dev(i)
            if prefetch and i + 1 < nseg:
                _seg_dev(i + 1)
        a, b = dsegs["segs"][i]
        # dR0 is never donated (the rescue walk re-reads it); only the
        # pipeline-intermediate carried sets are
        use_donate = donate and i > 0
        try:
            if use_donate:
                if run_d is None:
                    run_d = _batch_call(B, W, M, S, H, O1, seg, n_pass,
                                        interpret, cdt, True, tile)
                ck, R_cur = run_d(a, b, dsegs["dP"], R_cur)
                obs.count("donate.reuse")
            else:
                ck, R_cur = run(a, b, dsegs["dP"], R_cur)
        except Exception as e:                          # noqa: BLE001
            # packedness of what's actually resident, not the env gate:
            # a rescue re-entry may carry dense segments from a prior
            # call's fallback while the gate still reads open
            packed_wire = (
                getattr(dsegs["dR0"], "dtype", None) == np.uint8
                or getattr(a, "dtype", None) == np.uint8)

            def _dense_recover(exc):
                """ONE `packed-xfer` record: re-materialize the round-5
                dense format host-side (f32 seed, signed narrow ops —
                every built segment too, so the record covers the rest
                of the walk), account the re-uploads, and re-walk
                segments 0..i undonated from the seed. The record lands
                only after the dense re-walk succeeds — a failure that
                persists dense was never the packed wire's fault."""
                nonlocal sextet
                extra = 0
                if getattr(dsegs["dR0"], "dtype", None) == np.uint8:
                    dense = transfer.unpack_bool_host(
                        np.asarray(dsegs["dR0"]), M * HS)
                    dsegs["dR0"] = jnp.asarray(
                        dense.reshape(M, HS).astype(np.float32),
                        dtype=cdt)
                    extra += M * HS * (2 if cdt == "bfloat16" else 4)
                if getattr(dsegs["segs"][i][0], "dtype",
                           None) == np.uint8:
                    n_built = len(dsegs["segs"])
                    dsegs["segs"] = [jax.device_put(_seg_host(k))
                                     for k in range(n_built)]
                    # dense rebuilds of the built segments re-cross the
                    # link, and the segments still to come now cross
                    # dense instead of sextet-packed
                    o_b = seg * HW * ops_flat.dtype.itemsize
                    extra += n_built * (o_b + seg * H
                                        * rs_rh.dtype.itemsize)
                    extra += (nseg - n_built) * (
                        o_b - transfer.sextet_bytes(seg * HW))
                sextet = False
                # the counters AND this walk's diag must see what the
                # link actually carried, or the fallback run would
                # report a diet it did not get
                transfer.count_put(extra, 0)
                obs.count("lockstep.transfer_bytes", extra)
                a0, b0 = dsegs["xfer"]
                dsegs["xfer"] = (a0 + extra, b0)
                R = dsegs["dR0"]
                for k in range(i):
                    _c, R = run(*dsegs["segs"][k], dsegs["dP"], R)
                out = run(*dsegs["segs"][i], dsegs["dP"], R)
                obs.engine_fallback("packed-xfer", type(exc).__name__)
                return out

            if use_donate:
                # exactly one `donate` record; the donated carry may
                # already have been consumed by the failed dispatch:
                # recompute it from the never-donated seed through the
                # undonated jit
                obs.engine_fallback("donate", type(e).__name__)
                donate = False
                try:
                    R_cur = dsegs["dR0"]
                    for k in range(i):
                        _ck, R_cur = run(*dsegs["segs"][k],
                                         dsegs["dP"], R_cur)
                    ck, R_cur = run(a, b, dsegs["dP"], R_cur)
                except Exception as e2:                 # noqa: BLE001
                    # not donation after all: the packed wire itself
                    # fails on this backend — degrade it to dense
                    if not packed_wire:
                        raise
                    ck, R_cur = _dense_recover(e2)
            elif packed_wire:
                ck, R_cur = _dense_recover(e)
            else:
                raise
        ckpts.append(ck)
    return ckpts, R_cur


class BatchInflight:
    """A dispatched-but-unfetched lockstep walk: every device program
    is queued, no result has crossed the wire. Produced by
    :func:`dispatch_returns_batch`, consumed by
    :func:`collect_returns_batch` — the split lets a scheduler queue
    the NEXT group's walk (and pay its marshalling/compile host time)
    before fetching the previous group's verdicts, overlapping host
    work with device walks across bucket groups. ``device`` (when set)
    is the mesh device this group's lane block walks on. ``body``
    records the kernel body this group walked (``dense`` = the Pallas
    batch kernel, ``word`` = the vmapped word-packed scan); a word
    walk carries its queued device results in ``word_out``."""
    __slots__ = ("P", "geom", "host_args", "R_lens", "dsegs",
                 "ckpts", "final", "interpret", "device", "degraded",
                 "body", "word_out", "key_W")

    def __init__(self, P, geom, host_args, R_lens, dsegs, ckpts,
                 final, interpret, device=None, key_W=None):
        self.P = P
        self.geom = geom
        self.host_args = host_args
        self.R_lens = R_lens
        self.key_W = key_W
        self.dsegs = dsegs
        self.ckpts = ckpts
        self.final = final
        self.interpret = interpret
        self.device = device
        # set by collect_returns_batch when a lazy-fetch fallback
        # degraded this walk's collect to eager full-array fetches
        self.degraded = False
        self.body = "dense"
        self.word_out = None


class BatchPrepared:
    """Marshalled-but-undispatched lockstep operands for one group:
    the output of :func:`prepare_returns_batch` (pure host work — numpy
    interleaving plus geometry; safe to run on the streaming prep
    thread, no jax calls), consumed by :func:`dispatch_prepared` on the
    dispatching thread. The prepare/dispatch split is what lets the
    streaming pipeline pack group g+1 while group g walks on device.
    A mesh scheduler sets ``device`` before dispatching to pin this
    group's lane block to one chip (None = jax's default device).
    ``body`` (None = resolve at dispatch: autotune winner, force
    gate, else dense) selects the kernel body this group walks.
    ``key_W`` holds each lane's own mask width (for ``group_diag``)."""
    __slots__ = ("P", "geom", "host_args", "R_lens", "interpret",
                 "device", "body", "key_W")

    def __init__(self, P, geom, host_args, R_lens, interpret,
                 device=None, body=None, key_W=None):
        self.P = P
        self.geom = geom
        self.host_args = host_args
        self.R_lens = R_lens
        self.key_W = key_W
        self.interpret = interpret
        self.device = device
        self.body = body


def prepare_returns_batch(P: np.ndarray, ret_slots: List[np.ndarray],
                          slot_ops: List[np.ndarray], M: int, *,
                          interpret: Optional[bool] = None,
                          key_W=None) -> BatchPrepared:
    """Host-only half of :func:`dispatch_returns_batch`: marshal H
    return streams into the lockstep layout without touching jax.
    ``key_W``: each stream's own mask width, where the caller knows it
    (:func:`group_diag` counts the group's W where it does not)."""
    if interpret is None:
        interpret = _INTERPRET_DEFAULT
    geom, host_args, R_lens = pack_batch_operands(
        P, ret_slots, slot_ops, M, interpret=interpret)
    return BatchPrepared(P, geom, host_args, R_lens, interpret,
                         key_W=key_W)


def _pipe_walk_on(device, host_args, geom, n_pass: int, interpret: bool,
                  dsegs: dict):
    """:func:`_pipe_walk_b` with every put/compile/dispatch committed to
    ``device`` (None = default device): the single-chip kernel is the
    per-shard body of the mesh lockstep lane — jax routes the jitted
    walk to wherever its operands are committed, so N shards queued on
    N devices walk concurrently."""
    with _on_device(device):
        return _pipe_walk_b(host_args, geom, n_pass, interpret, dsegs,
                            device=device)


def _lockstep_body(geom) -> str:
    """Kernel-body selection for one lockstep dispatch group: the
    persisted autotune table first (a ``lockstep`` winner recorded by
    ``tools/batch_width.py --bodies``), then the
    ``JEPSEN_TPU_WORD_POSTHOC=1`` force, else the Pallas batch kernel
    (``dense``). ``word`` only where the word body admits."""
    from jepsen_tpu.checkers import autotune, reach_word

    _B, W, M, S, H, _O1, _R_pad = geom
    if not (reach_word.enabled() and reach_word.admits(S, W, M)):
        return "dense"
    if os.environ.get("JEPSEN_TPU_WORD_POSTHOC"):
        return "word"
    w = autotune.winner("lockstep", autotune.lockstep_key(S, W, M, H))
    return w if w in ("word", "dense") else "dense"


def _dispatch_words(prep: BatchPrepared) -> BatchInflight:
    """Queue the word-packed lockstep walk (the ``reach_word`` body):
    one shared transition table derived from P, per-lane word-vector
    frontiers, the whole group as ONE vmapped scan — nothing fetched
    (the queued device results ride ``word_out`` into the collect)."""
    import jax.numpy as jnp

    from jepsen_tpu.checkers import reach_word

    _B, W, M, S, H, _O1, R_pad = prep.geom
    ops_flat, rs_rh, P, _R0 = prep.host_args
    Tpad = reach_word.pad_table(reach_word.table_from_P(P))
    NW = reach_word.n_words(M)
    R0w = np.zeros((H, S, NW), np.uint32)
    R0w[:, 0, 0] = 1                     # mask 0, state 0 per lane
    rs_hr = np.ascontiguousarray(rs_rh.T.astype(np.int32))
    so_hrw = np.ascontiguousarray(np.swapaxes(
        np.asarray(ops_flat).reshape(R_pad, H, W), 0, 1)
        .astype(np.int32))
    transfer.count_put(
        int(Tpad.nbytes + R0w.nbytes + rs_hr.nbytes + so_hrw.nbytes),
        int(Tpad.nbytes + H * S * M * 4
            + (rs_hr.size + so_hrw.size) * 4))

    with _on_device(prep.device):
        out = reach_word._jitted_walk_words_batch()(
            jnp.asarray(Tpad), jnp.asarray(R0w), jnp.asarray(rs_hr),
            jnp.asarray(so_hrw))
    obs.count("lockstep.word_groups")
    fl = BatchInflight(prep.P, prep.geom, prep.host_args, prep.R_lens,
                       {}, [], None, prep.interpret,
                       device=prep.device, key_W=prep.key_W)
    fl.body = "word"
    fl.word_out = out
    return fl


def dispatch_prepared(prep: BatchPrepared) -> BatchInflight:
    """Queue a prepared group's walk (device puts + compiles +
    dispatches — all jax work) without fetching anything. Pair with
    :func:`collect_returns_batch`. The kernel body is resolved here
    (:func:`_lockstep_body` unless the caller pinned ``prep.body``);
    a word-body dispatch failure records exactly one ``word-walk``
    obs fallback and the group walks the dense Pallas kernel."""
    body = prep.body if prep.body in ("word", "dense") \
        else _lockstep_body(prep.geom)
    if body == "word":
        try:
            return _dispatch_words(prep)
        except Exception as e:                          # noqa: BLE001
            obs.engine_fallback("word-walk", type(e).__name__,
                                lanes=prep.geom[4])
    W = prep.geom[1]
    n_fast = _first_passes(W, prep.geom[2], prep.geom[3])
    dsegs: dict = {}
    ckpts, final = _pipe_walk_on(prep.device, prep.host_args, prep.geom,
                                 n_fast, prep.interpret, dsegs)
    if _wide_tile(W, prep.geom[2], prep.geom[3]):
        obs.count("lockstep.wide_groups")
    return BatchInflight(prep.P, prep.geom, prep.host_args, prep.R_lens,
                         dsegs, ckpts, final, prep.interpret,
                         device=prep.device, key_W=prep.key_W)


def dispatch_returns_batch(P: np.ndarray, ret_slots: List[np.ndarray],
                           slot_ops: List[np.ndarray], M: int, *,
                           interpret: Optional[bool] = None
                           ) -> BatchInflight:
    """Marshal + queue the lockstep walk of H return streams without
    fetching anything. Pair with :func:`collect_returns_batch`."""
    return dispatch_prepared(prepare_returns_batch(
        P, ret_slots, slot_ops, M, interpret=interpret))


@functools.cache
def _alive_lanes_call(H: int, S: int):
    """On-device verdict reduction for the lockstep walk: H alive bits
    cross the wire instead of the full [M, H*S] f32 config set — the
    fixed few-byte summary the valid-history path fetches; the full
    arrays (final set, block checkpoints) cross only when a lane is
    invalid and witness localization needs them."""
    import jax
    import jax.numpy as jnp
    return jax.jit(
        lambda f: jnp.max(f.reshape(f.shape[0], H, S), axis=(0, 2))
        > 0.5)


def collect_returns_batch(fl: BatchInflight) -> np.ndarray:
    """Fetch an in-flight lockstep walk's verdicts: ``dead[H]`` — per
    history, the first return index at which its config set emptied,
    or -1 if linearizable (exact rescue + localization as
    :func:`walk_returns_batch`). With lazy fetch (the default) the
    valid path fetches only H on-device-reduced alive bits; eager
    (``JEPSEN_TPU_NO_LAZY_FETCH=1``) fetches the full final set as in
    round 5 — verdicts are bit-identical either way."""
    P, interpret = fl.P, fl.interpret
    geom, host_args, R_lens, dsegs = (fl.geom, fl.host_args, fl.R_lens,
                                      fl.dsegs)
    B, W, M, S, H, O1, R_pad = geom
    if fl.body == "word":
        try:
            _R, any_dead, first = fl.word_out
            any_np = np.asarray(any_dead)
            first_np = np.asarray(first)
            dead = np.full(len(R_lens), -1, np.int64)
            for h in np.nonzero(any_np[:len(R_lens)])[0]:
                # exact per-step death (identity pads cannot kill a
                # live set), clamped to the lane's real length
                dead[int(h)] = min(int(first_np[int(h)]),
                                   max(int(R_lens[int(h)]) - 1, 0))
            return dead
        except Exception as e:                          # noqa: BLE001
            # the queued word walk died at fetch (jax dispatch is
            # async — errors surface at first consumption): one
            # record, then the group re-walks the dense body from the
            # retained host operands
            obs.engine_fallback("word-walk", type(e).__name__,
                                lanes=H, collect=True)
            redo = BatchPrepared(P, geom, host_args, R_lens,
                                 interpret, device=fl.device,
                                 body="dense", key_W=fl.key_W)
            return collect_returns_batch(dispatch_prepared(redo))
    n_fast = _first_passes(W, M, S)
    ckpts, final = fl.ckpts, fl.final
    HS = H * S
    lazy = transfer.lazy_fetch_enabled()

    def _alive_of(fin) -> np.ndarray:
        nonlocal lazy

        def _eager(fn):
            obs.count("fetch.eager")
            fn = np.asarray(fn, np.float32)
            return np.array([fn[:, h * S:(h + 1) * S].any()
                             for h in range(H)])

        if lazy:
            try:
                a = np.asarray(_alive_lanes_call(H, S)(fin))
                obs.count("fetch.lazy")
                return a
            except Exception as e:                      # noqa: BLE001
                # fetch the final set FIRST: jax dispatch is async, so
                # a walk error also surfaces at first consumption — a
                # poisoned result propagates here and is NOT recorded
                # as a lazy-fetch failure. Otherwise exactly one
                # fallback; this collect degrades to eager
                fn = np.asarray(fin)
                obs.engine_fallback("lazy-fetch", type(e).__name__)
                lazy = False
                # the schedulers' diag reports the protocol the
                # verdicts ACTUALLY crossed on, not the env gate
                fl.degraded = True
                return _eager(fn)
        return _eager(np.asarray(fin))

    alive = _alive_of(final)                     # the ONE round-trip
    if not alive.all() and n_fast < W:
        # capped-ladder deaths may be false: decide with the exact
        # W-pass walk (reuses the uploaded device segments)
        obs.count("lockstep.exact_rescue")
        ckpts, final = _pipe_walk_on(fl.device, host_args, geom, W,
                                     interpret, dsegs)
        alive = _alive_of(final)
    # pad lanes (wide-M groups) stay alive: only the real lanes answer
    alive = alive[:len(R_lens)]
    dead = np.full(len(R_lens), -1, np.int64)
    if alive.all():
        return dead
    # localization: fetch the block checkpoints once, then re-walk the
    # death block of each dead history in ITS OWN geometry
    ckpt_np = np.concatenate([np.asarray(c, np.float32) for c in ckpts])
    n_blocks = R_pad // B
    ckpt_np = ckpt_np[:n_blocks]                 # [blocks, M, HS]
    ops_rhw = np.asarray(host_args[0]).reshape(R_pad, H, W)
    rs_rh = host_args[1]
    for h in np.nonzero(~alive)[0]:
        col = ckpt_np[:, :, h * S:(h + 1) * S]   # [blocks, M, S]
        occ = col.reshape(n_blocks, -1).any(axis=1)
        first_empty = int(np.argmin(occ)) if not occ.all() else n_blocks
        blk = max(0, first_empty - 1)
        # a mesh group re-walks on ITS chip, not the default device
        with _on_device(fl.device):
            dead[h] = _refine_dead(
                P, W, M,
                np.ascontiguousarray(rs_rh[:, h].astype(np.int32)),
                np.ascontiguousarray(ops_rhw[:, h, :]),
                col[blk].T > 0.5, blk * B,
                min(B, max(1, R_lens[h] - blk * B)))
    return dead


def _on_device(device):
    """``jax.default_device(device)``, or no-op for None."""
    if device is None:
        return contextlib.nullcontext()
    import jax
    return jax.default_device(device)


def walk_returns_batch(P: np.ndarray, ret_slots: List[np.ndarray],
                       slot_ops: List[np.ndarray], M: int, *,
                       interpret: bool = False) -> np.ndarray:
    """Walk H independent return streams in lockstep; returns
    ``dead[H]`` — per history, the first return index at which its
    config set emptied, or -1 if linearizable. Exact: capped fast
    ladder first (sound for "valid"), per-history exact rescue +
    block-checkpoint refinement on death, identical verdicts and
    indices to H single-history walks. One-shot form of the
    :func:`dispatch_returns_batch` / :func:`collect_returns_batch`
    pair."""
    return collect_returns_batch(dispatch_returns_batch(
        P, ret_slots, slot_ops, M, interpret=interpret))


def walk_returns_batch_sharded(P: np.ndarray,
                               ret_slots: List[np.ndarray],
                               slot_ops: List[np.ndarray], M: int,
                               devices: Sequence, *,
                               interpret: Optional[bool] = None
                               ) -> np.ndarray:
    """Walk H return streams in lockstep with the LANE axis sharded
    over ``devices``: the lane blocks split per device
    (:func:`shard_groups_for_mesh` — the count padded to even splits
    by replicating a lane), each block's walk queued on its own chip
    with the single-chip kernel as the per-shard body, and ALL shards
    dispatched before any verdict is fetched — so N devices walk
    concurrently. Verdicts are bit-identical to
    :func:`walk_returns_batch`: every lane walks exactly the stream it
    would walk single-chip, just on its own device."""
    devs = list(devices)
    H = len(ret_slots)
    groups, pad = shard_groups_for_mesh([list(range(H))], len(devs))
    inflight = []
    for k, g in enumerate(groups):
        prep = prepare_returns_batch(
            P, [ret_slots[h] for h in g], [slot_ops[h] for h in g], M,
            interpret=interpret)
        prep.device = devs[k % len(devs)]
        inflight.append((g, dispatch_prepared(prep)))
    dead = np.full(H, -1, np.int64)
    for g, fl in inflight:
        dead[np.asarray(g, np.int64)] = collect_returns_batch(fl)
    if pad:
        # counted after the collect loop: once per COMPLETED walk, the
        # same contract as the schedulers' _lockstep_accounting
        obs.count("lockstep.mesh.pad_lanes", pad)
    return dead
