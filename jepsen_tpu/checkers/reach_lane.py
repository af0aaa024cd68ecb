"""Third-generation Pallas TPU kernel for the dense-reachability
returns walk — the single-history hot path.

Generation history (all measured on one v5-lite chip at the headline
config: S=8 states, W=5 slots, M=32 masks, cas-100k = 73.7k returns):

- gen 1 (:mod:`.reach_pallas`): 2 unrolled passes + fixpoint
  ``while_loop`` — ~1.28 µs/return (~600 ns was while machinery).
- gen 2 (round 2 of this module): 5 UNCONDITIONAL Jacobi fire passes
  (no data-dependent control flow at all), software-pipelined
  transition gather, block-checkpoint death detection —
  ~0.96-1.19 µs/return.
- gen 3 (this round): the **pending-count gate ladder**
  (:func:`_ladder_fire`). Between returns, a fire chain linearizes
  DISTINCT pending slots, so chains are ≤ c_r (the pending count at
  return r) long and c_r monotone passes reach the closure exactly.
  c_r is host-known: the kernel runs 1 unconditional pass plus passes
  2..n_pass each under ``pl.when(c_r > passes_so_far)`` — executing
  exactly ``min(c_r, n_pass)`` passes per return. On benchmark
  histories E[c_r] ≈ 3.0 vs 5, and an untaken ``pl.when`` is ~free
  (a TAKEN when with an SMEM-scalar predicate and an R_scr-only body
  measured ~tens of ns — NOT the ~1.3 µs of the round-2 ablation's
  mid-pipeline data-dependent tail). Measured: **~0.74 µs/return
  exact** (54 ms kernel-only at cas-100k, vs the C++ WGL engine's
  74-190 ms band), with a 2× return-loop unroll worth ~10% more.

Round-3 ablations that LOST (kept in ``tools/ablate_lane.py``):
counts-semantics passes (drop the >0.5 compare+cast for adds,
+15-20%), projection as a gathered [M,M]@[M,S] matmul (+20%), a
pre-gathered HBM-streamed G operand replacing the in-kernel gather
(+15%), alternating-direction Gauss-Seidel sweeps at reduced pass
counts (the under-approximation dies on benchmark histories, paying
for both walks — confirming the round-2 finding that pass-count cuts
without the c_r bound don't survive).

Other structure is unchanged from gen 2: software-pipelined gather,
no per-return death check (block checkpoints + host refinement), the
``[M, S]`` layout (the transposed ``[S, M]``/lane-roll layout and
streamed operands measured worse — see the round-2 notes in git
history). For ``W > 5`` the fast walk caps the ladder at 5 passes
(sound: under-approximation + monotone emptiness ⇒ a surviving final
set still certifies "linearizable"); death rescues with the exact
``n_pass = W`` ladder.

Semantics are identical to ``reach._walk_returns`` (upstream analogue:
``knossos/src/knossos/linear.clj``'s per-event config-set advance);
the engine remains exact — no fingerprint hashing. ``interpret=True``
runs the kernel on CPU for differential tests.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu.checkers import transfer

_BLOCK = 1024
# ladder cap for the fast walk. Gates above a return's pending count
# are untaken (~free), so a higher cap costs W<=5 histories nothing at
# runtime while making W in (5, 8] histories EXACT in one walk (no
# sound-but-double fast+rescue dance); only compile size grows. W > 8
# keeps the capped fast walk + exact rescue.
_FAST_PASSES = 8

# returns per device dispatch when a should_abort hook is supplied: the
# walk then runs serially segment-by-segment (carried config set, one
# fetch per segment) so a losing competition engine frees the chip
# within ~one segment instead of holding it for the whole history. The
# non-abortable path stays a single fetch — no cost to the headline.
_ABORT_SEG = 32768

# the non-abortable walk splits put+dispatch into this many segments
# (still ONE fetch): the link is idle while the device walks a segment,
# so the next segment's operand upload rides under kernel execution
# (the hideable window is the kernel time; the gain is unmeasured on
# the chip)
_PIPE_NSEG = 4


class Aborted(RuntimeError):
    """The caller's ``should_abort`` fired between segments."""


def _idx_dtype(O1: int):
    """Narrowest signed dtype holding op indices in [-1, O1): the int32
    cast happens inside the jitted program, so the wire carries only
    these bytes — ``slot_ops`` is the dominant operand (R_pad*W
    entries), and at the headline config (O1=36) int8 halves total
    host->device transfer vs the former int16. Delegates to
    :func:`transfer.idx_dtype`, whose int32 overflow fallback bumps
    ``transfer.narrow_fallback``."""
    return transfer.idx_dtype(O1)


def _project(R, j, W: int, M: int, S: int):
    """Projection on the returning slot ``j``: keep configs that fired
    slot j (mask bit set), clearing the bit; ``j = -1`` (padding) is
    the identity. Scalar-predicate vector selects don't legalize in
    Mosaic, so blend the W static projections with 0/1 indicator
    multiplies — exactly one is hot (~30 ns measured)."""
    import jax.numpy as jnp

    acc = R * (j < 0).astype(jnp.float32)
    for jj in range(W):
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, S)
        taken = Rr[:, 1]
        p = jnp.stack([taken, jnp.zeros_like(taken)],
                      axis=1).reshape(M, S)
        acc = acc + p * (j == jj).astype(jnp.float32)
    return acc


def _ladder_fire(R_scr, R, pend_c, G_all, n_pass: int, W: int, M: int,
                 S: int):
    """Closure passes with the pending-count gate ladder: ONE
    unconditional fire pass, then passes 2..n_pass each under
    ``pl.when(pending_count > passes_so_far)``.

    Exactness: between returns, a fire chain sets one mask bit of a
    distinct pending slot per step, so chains are at most ``c_r`` (the
    pending count at return r) long and ``c_r`` monotone passes reach
    the closure. The ladder therefore executes exactly
    ``min(c_r, n_pass)`` passes — the full closure whenever
    ``n_pass >= W >= c_r``. On the cas-100k benchmark E[c_r] ≈ 3.0
    vs the round-2 kernel's 5 unconditional passes, and the untaken
    ``pl.when`` is ~free (measured: the ladder is ~30% faster
    end-to-end; a TAKEN when costs only ~tens of ns here, not the
    ~1.3 µs a mid-pipeline data-dependent tail was measured at —
    the predicate is an SMEM scalar and the body writes only R_scr).

    ``R_scr`` carries the set across gate bodies; returns the final R
    (read back from R_scr).
    """
    from jax.experimental import pallas as pl

    from jepsen_tpu.checkers.reach_pallas import _one_fire_pass

    R = _one_fire_pass(R, G_all, W, M, S)
    if n_pass <= 1:
        return R
    R_scr[:] = R
    for off in range(1, n_pass):
        def _deep():
            Rd = R_scr[:]
            R_scr[:] = _one_fire_pass(Rd, G_all, W, M, S)
        pl.when(pend_c > off)(_deep)
    return R_scr[:]


def _make_kernel(B: int, W: int, M: int, S: int, O1: int,
                 n_blocks: int, n_pass: int, unroll: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from jepsen_tpu.checkers.reach_pallas import _gather_G

    def kernel(ret_slot_ref, slot_ops_ref, pend_ref, P_ref, R0_ref,
               ckpt_ref, final_ref, R_scr, G_scr):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            R_scr[:] = R0_ref[:]

        ckpt_ref[0] = R_scr[:]                   # set at block START
        G_scr[0] = _gather_G(slot_ops_ref, P_ref, 0, W, O1)

        def one(k, R):
            j = ret_slot_ref[k]
            G_all = G_scr[k % 2]
            # prefetch the NEXT return's fire operand while this
            # return's MXU chain is in flight (G does not depend on R)
            kn = jnp.minimum(k + 1, B - 1)
            G_scr[(k + 1) % 2] = _gather_G(slot_ops_ref, P_ref, kn, W, O1)
            R = _ladder_fire(R_scr, R, pend_ref[k], G_all, n_pass,
                             W, M, S)
            return _project(R, j, W, M, S)

        def do_return(i, _):
            R = R_scr[:]
            for u in range(unroll):
                R = one(i * unroll + u, R)
            R_scr[:] = R
            return 0

        jax.lax.fori_loop(0, B // unroll, do_return, 0)

        @pl.when(step == n_blocks - 1)
        def _finish():
            final_ref[:] = R_scr[:]

    return kernel


@functools.cache
def _lane_call(B: int, W: int, M: int, S: int, O1: int, R_pad: int,
               n_pass: int, interpret: bool, donate: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_blocks = R_pad // B
    unroll = 2 if B % 2 == 0 else 1
    kernel = _make_kernel(B, W, M, S, O1, n_blocks, n_pass, unroll)
    call = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((B * W,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((O1, S, S), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((M, S), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, M, S), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((M, S), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, M, S), jnp.float32),
            jax.ShapeDtypeStruct((M, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((M, S), jnp.float32),
            pltpu.VMEM((2, S, W * S), jnp.float32),
        ],
        interpret=interpret,
    )

    def run(ret_slot, slot_ops, P, R0):
        if R0.dtype == jnp.uint8:
            # bit-packed config seed: 8 configs per wire byte, unpacked
            # on device where bandwidth is free (the transfer diet)
            R0 = jnp.unpackbits(R0, count=M * S).reshape(M, S) \
                    .astype(jnp.float32)
        if slot_ops.dtype == jnp.uint8:
            # 6-bit packed ops lane (4 values per 3 wire bytes): the
            # dense narrow format is SIGNED, so uint8 unambiguously
            # marks the packed lane
            slot_ops = transfer.unpack_sextet_jnp(slot_ops, R_pad * W)
        # pending count per return — the gate ladder's exact per-return
        # pass bound (fire chains set distinct pending slots, so c_r
        # passes close). Derived on device FROM THE NARROW wire array
        # (no eager int32 materialization before the reduce); the int32
        # upcast exists only as the kernel's SMEM operand.
        pend = jnp.sum((slot_ops.reshape(-1, W) >= 0).astype(jnp.int32),
                       axis=1)
        return call(ret_slot.astype(jnp.int32),
                    slot_ops.astype(jnp.int32), pend, P, R0)

    # donating the carried config set lets XLA recycle its HBM buffer
    # for the segment's `final` output (same [M, S] f32 geometry)
    # instead of reallocating per dispatch; only pipeline-intermediate
    # carries are donated (see _pipe_walk — dR0 must survive rescues)
    return jax.jit(run, donate_argnums=(3,)) if donate else jax.jit(run)


# -- keyed batch: many independent keys in one kernel ------------------------
#
# The per-key (`jepsen.independent`) hot path, with the same
# pending-count gate ladder as the single-history walk (exact
# min(c_r, n_pass) passes per return) and the software-pipelined
# gather. The per-return death check stays — per-key exact dead
# indices are the kernel's output — as do the key-boundary config-set
# resets (untaken pl.when is ~free; the reset fires once per key).

def _make_keyed_kernel(B: int, W: int, M: int, S: int, O1: int,
                       K: int, n_pass: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from jepsen_tpu.checkers.reach_pallas import _gather_G

    def kernel(ret_slot_ref, slot_ops_ref, pend_ref, key_ref, P_ref,
               dead_ref, R_scr, G_scr, prev_scr):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            prev_scr[0] = jnp.int32(-1)

            def ini(k, _):
                dead_ref[k] = jnp.int32(-1)
                return 0

            jax.lax.fori_loop(0, K, ini, 0)

        rows = jax.lax.broadcasted_iota(jnp.int32, (M, S), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (M, S), 1)
        R0 = jnp.logical_and(rows == 0, cols == 0).astype(jnp.float32)
        G_scr[0] = _gather_G(slot_ops_ref, P_ref, 0, W, O1)

        def do_return(b, _):
            r = step * B + b
            j = ret_slot_ref[b]
            key = key_ref[b]
            is_real = key >= 0

            @pl.when(jnp.logical_and(is_real, key != prev_scr[0]))
            def _new_key():
                R_scr[:] = R0
                prev_scr[0] = key

            G_all = G_scr[b % 2]
            bn = jnp.minimum(b + 1, B - 1)
            G_scr[(b + 1) % 2] = _gather_G(slot_ops_ref, P_ref, bn, W, O1)
            R = _ladder_fire(R_scr, R_scr[:], pend_ref[b], G_all,
                             n_pass, W, M, S)
            R = _project(R, j, W, M, S)
            kk = jnp.maximum(key, 0)

            @pl.when(jnp.logical_and(
                    is_real,
                    jnp.logical_and(jnp.sum(R) < 0.5, dead_ref[kk] < 0)))
            def _mark_dead():
                dead_ref[kk] = r

            R_scr[:] = R
            return 0

        jax.lax.fori_loop(0, B, do_return, 0)

    return kernel


@functools.cache
def _keyed_call(B: int, W: int, M: int, S: int, O1: int, N_pad: int,
                K_pad: int, n_pass: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _make_keyed_kernel(B, W, M, S, O1, K_pad, n_pass)
    call = pl.pallas_call(
        kernel,
        grid=(N_pad // B,),
        in_specs=[
            pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((B * W,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((O1, S, S), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            # constant index map: the block stays resident across the
            # sequential grid, accumulating per-key verdicts
            pl.BlockSpec((K_pad,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((K_pad,), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((M, S), jnp.float32),
            pltpu.VMEM((2, S, W * S), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )

    def run(ret_slot, slot_ops, key_id, P):
        if slot_ops.dtype == jnp.uint8:
            # 6-bit packed ops lane — see _lane_call.run
            slot_ops = transfer.unpack_sextet_jnp(slot_ops, N_pad * W)
        # pending counts derived on device from the narrow wire arrays
        # (see _lane_call.run)
        pend = jnp.sum((slot_ops.reshape(-1, W) >= 0).astype(jnp.int32),
                       axis=1)
        return call(ret_slot.astype(jnp.int32),
                    slot_ops.astype(jnp.int32), pend,
                    key_id.astype(jnp.int32), P)

    return jax.jit(run)


def walk_returns_keyed(P: np.ndarray, ret_slot: np.ndarray,
                       slot_ops: np.ndarray, key_id: np.ndarray,
                       n_keys: int, M: int, *,
                       interpret: bool = False) -> np.ndarray:
    """Walk the concatenation of ``n_keys`` return streams in one
    kernel; same contract as
    :func:`jepsen_tpu.checkers.reach_pallas.walk_returns_keyed`."""
    import jax

    from jepsen_tpu.checkers.reach import _bucket

    O1, S, _ = P.shape
    N = int(ret_slot.shape[0])
    W = int(slot_ops.shape[1])
    B = min(32, _BLOCK) if interpret else _BLOCK
    N_pad = max(B, _bucket(-(-max(N, 1) // B) * B, B))
    K_pad = max(8, _bucket(n_keys, 8))
    if N_pad != N:
        ret_slot = np.pad(ret_slot, (0, N_pad - N), constant_values=-1)
        slot_ops = np.pad(slot_ops, ((0, N_pad - N), (0, 0)),
                          constant_values=-1)
        key_id = np.pad(key_id, (0, N_pad - N), constant_values=-1)
    run = _keyed_call(B, W, M, S, O1, N_pad, K_pad, W, interpret)
    idx_dt = _idx_dtype(O1)
    # key ids ride the narrowest signed dtype holding [-1, K_pad) —
    # the in-jit upcast to the kernel's i32 SMEM operand is free
    key_dt = transfer.idx_dtype(K_pad) if transfer.packed_enabled() \
        else np.int32
    so_dense = np.ascontiguousarray(slot_ops.reshape(-1), idx_dt)
    so_flat = so_dense
    packed = transfer.packed_enabled() and transfer.sextet_ok(O1)
    if packed:
        # the dominant operand crosses 6-bit packed (4 ops / 3 bytes),
        # unpacked in-jit where bandwidth is free
        so_flat = transfer.pack_sextet(so_dense)
    host_args = (np.ascontiguousarray(ret_slot, np.int8),
                 so_flat,
                 np.ascontiguousarray(key_id, key_dt),
                 np.ascontiguousarray(P, np.float32))
    transfer.count_put(sum(a.nbytes for a in host_args),
                       N_pad * 4 + N_pad * W * 4 + N_pad * 4 + P.nbytes)
    args = jax.device_put(host_args)
    try:
        (dead,) = run(*args)
    except Exception as e:                              # noqa: BLE001
        if not (packed or key_dt != np.int32):
            raise
        # same packed-wire contract as the pipe walk: retry the round-5
        # dense format, count the re-upload, and land the ONE fallback
        # record only once the dense retry succeeds — a dense failure
        # too means packedness was not the cause, propagate unrecorded
        host_args = (host_args[0], so_dense,
                     np.ascontiguousarray(key_id, np.int32),
                     host_args[3])
        transfer.count_put(sum(a.nbytes for a in host_args), 0)
        (dead,) = run(*jax.device_put(host_args))
        obs.engine_fallback("packed-xfer", type(e).__name__)
    return np.asarray(dead)[:n_keys]


def _refine_dead(P_np, W: int, M: int, ret_slot, slot_ops,
                 R0_blk_sm: np.ndarray, start: int, n: int) -> int:
    """Exact dead return index within ``[start, start + n)``: re-walk
    that block one return at a time with the XLA walk from the carried
    block-start config set (``[S, M]`` bool). The block is padded with
    identity returns (slot -1, which cannot kill a live set) to a power
    of two, so a batch of dead keys with ragged block lengths compiles
    the walk a handful of times, not once per length."""
    import jax.numpy as jnp

    from jepsen_tpu.checkers import reach

    n_pad = reach._next_pow2(max(n, 8))
    rs_blk = np.full(n_pad, -1, np.int32)
    so_blk = np.full((n_pad, W), -1, np.int32)
    rs_blk[:n] = ret_slot[start:start + n]
    so_blk[:n] = slot_ops[start:start + n]
    xc, bm = reach._xor_bitmask(W, M)
    ptr1, _, alive, _ = reach._jitted_walk_returns_u1()(
        jnp.asarray(P_np), jnp.asarray(xc), jnp.asarray(bm),
        jnp.asarray(rs_blk), jnp.asarray(so_blk),
        jnp.asarray(R0_blk_sm))
    if bool(alive):                     # shouldn't happen; be conservative
        return start + n - 1
    return start + int(ptr1) - 1


def pack_operands(P: np.ndarray, ret_slot: np.ndarray,
                  slot_ops: np.ndarray, R0_sm: np.ndarray, *,
                  interpret: bool = False):
    """Marshal host operands for the lane walk: block-size selection,
    bucketed padding, narrow index dtypes, and the ``[M, S]`` config
    layout. Returns ``(geometry, padded_ret_slot, padded_slot_ops,
    host_args)`` where ``host_args`` feed the jitted program from
    :func:`_lane_call` directly. Shared by :func:`walk_returns` and the
    kernel probe in ``bench.py`` so the two can never drift."""
    from jepsen_tpu.checkers.reach import _bucket

    O1, S, _ = P.shape
    R_real = int(ret_slot.shape[0])
    W = int(slot_ops.shape[1])
    M = int(R0_sm.shape[1])
    # XLA tiles 1-D int SMEM operands at T(1024), so compiled blocks
    # must be 1024; the interpreter has no tiling and a small block
    # keeps the per-call padding short in differential tests
    B = min(32, _BLOCK) if interpret else _BLOCK
    R_pad = max(B, _bucket(-(-max(R_real, 1) // B) * B, B))
    if R_pad != R_real:
        ret_slot = np.pad(ret_slot, (0, R_pad - R_real),
                          constant_values=-1)
        slot_ops = np.pad(slot_ops, ((0, R_pad - R_real), (0, 0)),
                          constant_values=-1)
    idx_dt = _idx_dtype(O1)
    # the pending count per return (the gate ladder's exact per-return
    # pass bound) is NOT shipped: it is derived from slot_ops by a
    # trivial XLA reduce on device (see _lane_call.run), saving R_pad
    # wire bytes per check. The config seed crosses bit-packed
    # (8 configs/byte, unpacked on device) unless the diet is off.
    if transfer.packed_enabled():
        r0_wire = transfer.pack_bool(R0_sm.T)
    else:
        r0_wire = np.ascontiguousarray(R0_sm.T, np.float32)
    host_args = (np.ascontiguousarray(ret_slot, np.int8),
                 np.ascontiguousarray(slot_ops.reshape(-1), idx_dt),
                 np.ascontiguousarray(P, np.float32),
                 r0_wire)
    geom = (B, W, M, S, O1, R_pad)
    return geom, ret_slot, slot_ops, host_args


def _walk_segmented(host_args, geom, n_pass: int, interpret: bool,
                    should_abort, R_real: int):
    """Abortable serial drive: ``_ABORT_SEG``-return segments with the
    config set carried across dispatches and ONE fetch per segment (the
    fetch doubles as early death exit). Returns ``(dead, final_np)``
    mirroring the single-dispatch flow; raises :class:`Aborted` between
    segments when the hook fires."""
    import jax

    B, W, M, S, O1, R_pad = geom
    ret_slot, slot_ops_flat, P, R0 = host_args
    dP = jax.device_put(P)
    R_cur = jax.device_put(R0)
    transfer.count_put(
        int(ret_slot.nbytes) + int(slot_ops_flat.nbytes)
        + int(P.nbytes) + int(R0.nbytes),
        blanket_bytes(geom, P.nbytes))
    base = 0
    while base < R_pad:
        if should_abort():
            raise Aborted()
        seg = min(_ABORT_SEG, R_pad - base)
        run = _lane_call(B, W, M, S, O1, seg, n_pass, interpret)
        try:
            ckpt, final = run(ret_slot[base:base + seg],
                              slot_ops_flat[base * W:(base + seg) * W],
                              dP, R_cur)
        except Exception as e:                          # noqa: BLE001
            # only the first dispatch consumes the bit-packed seed;
            # same packed-wire contract as the pipe walk: ONE fallback
            # record, dense retry, re-upload counted
            if getattr(R_cur, "dtype", None) != np.uint8:
                raise
            dense = transfer.unpack_bool_host(np.asarray(R_cur), M * S)
            R_cur = jax.device_put(
                dense.reshape(M, S).astype(np.float32))
            transfer.count_put(M * S * 4, 0)
            ckpt, final = run(ret_slot[base:base + seg],
                              slot_ops_flat[base * W:(base + seg) * W],
                              dP, R_cur)
            # dense retry succeeded → the packed seed was at fault:
            # land the ONE fallback record (a dense failure propagates
            # unrecorded — backend breakage, not the packed wire)
            obs.engine_fallback("packed-xfer", type(e).__name__)
        final_np = np.asarray(final)
        if not final_np.any():
            # dead in this segment: locate the first empty checkpoint
            ckpt_np = np.asarray(ckpt)
            occupied = ckpt_np.reshape(ckpt_np.shape[0], -1).any(axis=1)
            first_empty = int(np.argmin(occupied)) \
                if not occupied.all() else ckpt_np.shape[0]
            blk = max(0, first_empty - 1)
            start = base + blk * B
            dead = _refine_dead(
                P, W, M,
                np.asarray(ret_slot),
                np.asarray(slot_ops_flat).reshape(R_pad, W),
                ckpt_np[blk].T > 0.5, start,
                min(B, max(1, R_real - start)))
            return dead, final_np
        R_cur = final
        base += seg
    return -1, np.asarray(R_cur)


def _pipe_geom(B: int, R_pad: int,
               nseg: Optional[int] = None) -> Tuple[int, int]:
    """Segment size (returns) and count for the pipelined dispatch.
    Shared by :func:`_pipe_walk` and the ``bench.py`` kernel probe so
    the probe times exactly the programs production dispatches. Applies
    in interpret mode too (differential tests then cover the
    multi-segment path whenever the history is long enough).
    ``nseg`` overrides the target segment count (the batch walk's
    operand set is H× larger, so it pipelines finer). Degrades
    gracefully: a walk too short for the target halves the segment
    count until ≥2 blocks per segment remain (instead of dropping
    straight to a single unpipelined put)."""
    want = _PIPE_NSEG if nseg is None else nseg
    n_blocks = R_pad // B
    nseg = want
    while nseg > 1 and n_blocks < 2 * nseg:
        nseg //= 2
    segb = -(-n_blocks // nseg)          # blocks per segment
    return segb * B, -(-n_blocks // segb)


def blanket_bytes(geom, p_nbytes: int) -> int:
    """Bytes of the dtype-blind blanket int32/f32 single-history
    operand set — the upper bound a format-unaware marshaller would
    ship, and the unpacked side of every :func:`transfer.count_put`
    pair (shared with ``bench.py``'s probes so the baseline cannot
    drift). NOTE: round 5 already shipped the integer lanes narrow
    (int8 ``ret_slot``, ``_idx_dtype`` ops); the shipped-wire
    comparison is :func:`round5_bytes`, and run-over-run bench
    ``transfer_bytes`` values compare actual wire to actual wire."""
    _B, W, M, S, _O1, R_pad = geom
    return R_pad * 4 + R_pad * W * 4 + int(p_nbytes) + M * S * 4


def round5_bytes(geom, p_nbytes: int) -> int:
    """Bytes the ROUND-5 wire actually shipped for this operand set
    (narrow ints, f32 seed, f32 P) — the honest upload-side baseline
    for \"how much did round 6 save\": the diet's upload wins over it
    are the 6-bit ops lane and the bit-packed seed; the larger round-6
    win is on the fetch side (one reduced verdict byte instead of the
    [M, S] f32 final set)."""
    _B, W, M, S, O1, R_pad = geom
    idx_sz = np.dtype(transfer.idx_dtype(O1, count=False)).itemsize
    return R_pad * 1 + R_pad * W * idx_sz + int(p_nbytes) + M * S * 4


def pack_ops_wire(geom, slot_ops_flat) -> np.ndarray:
    """The ops lane exactly as :func:`_pipe_walk` uploads it: 6-bit
    packed per segment, ragged tail identity-padded, concatenated.
    ``bench.py``'s put-observer moves this so the bytes it times are
    the bytes :func:`wire_bytes` accounts."""
    B, W, _M, _S, _O1, R_pad = geom
    seg, _nseg = _pipe_geom(B, R_pad)
    parts = []
    for lo in range(0, R_pad, seg):
        hi = min(lo + seg, R_pad)
        so = slot_ops_flat[lo * W:hi * W]
        if hi - lo < seg:
            so = np.pad(so, (0, (seg - (hi - lo)) * W),
                        constant_values=-1)
        parts.append(transfer.pack_sextet(so))
    return np.concatenate(parts)


def wire_bytes(geom, host_args) -> int:
    """Actual host→device bytes :func:`_pipe_walk` moves for this
    operand set: the 6-bit ops lane packs per segment (so the segment
    slices stay byte-aligned), everything else crosses as marshalled
    by :func:`pack_operands`. Shared with ``bench.py``'s probes so the
    measurement can never drift from production accounting."""
    B, W, M, S, O1, R_pad = geom
    ret_slot, slot_ops_flat, P, R0 = host_args
    if transfer.packed_enabled() and transfer.sextet_ok(O1):
        seg, nseg = _pipe_geom(B, R_pad)
        ops_b = nseg * transfer.sextet_bytes(seg * W)
    else:
        ops_b = int(slot_ops_flat.nbytes)
    return int(ret_slot.nbytes) + ops_b + int(P.nbytes) \
        + int(R0.nbytes)


def _pipe_walk(host_args, geom, n_pass: int, interpret: bool,
               dsegs: dict):
    """Put + dispatch the walk in :data:`_PIPE_NSEG` segments with the
    config set carried on device and NO intermediate fetch: while the
    device walks segment *i*, segment *i+1*'s operands stream over the
    otherwise-idle link. ``dsegs`` caches the per-segment device arrays
    so a rescue walk (different pass count, same operands) re-dispatches
    without re-uploading. The dominant ``slot_ops`` operand crosses
    6-bit packed (4 ops per 3 wire bytes, per segment) whenever the
    alphabet fits the sextet lane. Returns ``(ckpts, final)`` — a list
    of per-segment device checkpoint arrays (block starts,
    concatenation equals the single-dispatch checkpoint stream) and the
    final device config set. Nothing here blocks; the caller fetches."""
    import jax

    B, W, M, S, O1, R_pad = geom
    ret_slot, slot_ops_flat, P, R0 = host_args
    seg, nseg = _pipe_geom(B, R_pad)
    run = _lane_call(B, W, M, S, O1, seg, n_pass, interpret)
    run_d = None
    donate = transfer.donate_enabled()
    sextet = transfer.packed_enabled() and transfer.sextet_ok(O1)

    def _seg_host(k: int):
        """Segment ``k``'s host operands in the dense narrow format."""
        lo, hi = k * seg, min((k + 1) * seg, R_pad)
        rs_seg = ret_slot[lo:hi]
        so_seg = slot_ops_flat[lo * W:hi * W]
        if hi - lo < seg:                # ragged tail: identity pad rows
            rs_seg = np.pad(rs_seg, (0, seg - (hi - lo)),
                            constant_values=-1)
            so_seg = np.pad(so_seg, (0, (seg - (hi - lo)) * W),
                            constant_values=-1)
        return (np.ascontiguousarray(rs_seg),
                np.ascontiguousarray(so_seg))

    fresh = "segs" not in dsegs
    if fresh:
        # plain put, not transfer.cached_put: every check_packed builds
        # a fresh P so an identity-keyed hit never happens here, while
        # the cache would pin dead (host, device) P pairs across checks
        # — only the lockstep path (one P per group sequence) caches
        dsegs["dP"] = jax.device_put(P)
        dsegs["segs"] = []
        dsegs["dR0"] = jax.device_put(R0)
        # wire accounting: bytes this upload actually moves vs the
        # blanket int32/f32 format the diet replaced
        transfer.count_put(wire_bytes(geom, host_args),
                           blanket_bytes(geom, P.nbytes))
    R_cur = dsegs["dR0"]
    ckpts = []
    for i in range(nseg):
        if fresh:
            rs_seg, so_seg = _seg_host(i)
            dsegs["segs"].append(jax.device_put(
                (rs_seg,
                 transfer.pack_sextet(so_seg) if sextet else so_seg)))
        a, b = dsegs["segs"][i]
        # only pipeline-INTERMEDIATE carries are donated: dR0 must
        # survive for the rescue walk's re-dispatch, and segment i>0's
        # input is the previous segment's final, referenced nowhere
        # else once consumed
        use_donate = donate and i > 0
        try:
            if use_donate:
                if run_d is None:
                    run_d = _lane_call(B, W, M, S, O1, seg, n_pass,
                                       interpret, True)
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
                or getattr(b, "dtype", None) == np.uint8)

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
                        np.asarray(dsegs["dR0"]), M * S)
                    dsegs["dR0"] = jax.device_put(
                        dense.reshape(M, S).astype(np.float32))
                    extra += M * S * 4
                if getattr(dsegs["segs"][i][1], "dtype",
                           None) == np.uint8:
                    n_built = len(dsegs["segs"])
                    dsegs["segs"] = [jax.device_put(_seg_host(k))
                                     for k in range(n_built)]
                    # dense rebuilds of the built segments re-cross the
                    # link, and the segments still to come now cross
                    # dense instead of sextet-packed
                    so_b = seg * W * slot_ops_flat.dtype.itemsize
                    extra += n_built * (seg * ret_slot.dtype.itemsize
                                        + so_b)
                    extra += (nseg - n_built) * (
                        so_b - transfer.sextet_bytes(seg * W))
                sextet = False
                transfer.count_put(extra, 0)
                R = dsegs["dR0"]
                for k in range(i):
                    _c, R = run(*dsegs["segs"][k], dsegs["dP"], R)
                out = run(*dsegs["segs"][i], dsegs["dP"], R)
                obs.engine_fallback("packed-xfer", type(exc).__name__)
                return out

            if use_donate:
                # exactly one `donate` record; the rest of the walk
                # degrades to the undonated round-5 dispatch. The
                # donated carry may already have been consumed by the
                # failed dispatch, so recompute it from the never-
                # donated seed through the undonated jit
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


@functools.cache
def _jit_any():
    """On-device verdict reduction: ONE boolean crosses the wire
    instead of the full [M, S] config set (the lazy-fetch half of the
    transfer diet; the full set is fetched only when a consumer —
    witness decode, ``fetch_R`` — actually needs it)."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda f: jnp.any(f > 0.5))


def _pipe_ckpt_np(ckpts, n_blocks: int) -> np.ndarray:
    """Fetch and concatenate the per-segment checkpoint streams,
    trimmed to the real block count (the ragged tail's pad blocks carry
    copies of the final set). Only the death path pays these fetches."""
    return np.concatenate([np.asarray(c) for c in ckpts])[:n_blocks]


def walk_returns(P: np.ndarray, ret_slot: np.ndarray,
                 slot_ops: np.ndarray, R0_sm: np.ndarray, *,
                 interpret: bool = False,
                 fetch_R: bool = True,
                 should_abort=None) -> Tuple[int, Optional[np.ndarray]]:
    """Run the full returns walk on device; same contract as
    :func:`jepsen_tpu.checkers.reach_pallas.walk_returns`.

    ``P`` f32[O1, S, S] (last row the all-zero sentinel); ``ret_slot``
    i32[R]; ``slot_ops`` i32[R, W]; ``R0_sm`` bool[S, M]. Returns
    ``(dead, R_final)``: ``dead`` is the first return index at which
    the config set emptied (-1 if linearizable) and ``R_final`` the
    final config set as bool[S, M] (``None`` on invalid histories or
    with ``fetch_R=False`` — the verdict is in ``dead``). With
    ``should_abort``, the walk dispatches in :data:`_ABORT_SEG`-return
    segments, checks the hook between them, and raises
    :class:`Aborted` when it fires (upstream ``knossos.search`` abort
    semantics).
    """
    import jax

    R_real = int(ret_slot.shape[0])
    geom, ret_slot, slot_ops, host_args = pack_operands(
        P, ret_slot, slot_ops, R0_sm, interpret=interpret)
    B, W, M, S, O1, R_pad = geom
    n_fast = min(W, _FAST_PASSES)
    if should_abort is not None:
        dead, final_np = _walk_segmented(host_args, geom, n_fast,
                                         interpret, should_abort, R_real)
        exact = n_fast >= W
        if dead >= 0 and not exact:
            # possible false death of the capped ladder: decide exactly
            dead, final_np = _walk_segmented(host_args, geom, W,
                                             interpret, should_abort,
                                             R_real)
            exact = True
        if dead >= 0:
            return dead, None
        if not exact and fetch_R:
            _, final_np = _walk_segmented(host_args, geom, W, interpret,
                                          should_abort, R_real)
        return -1, (final_np > 0.5).T if fetch_R else None
    dsegs: dict = {}                     # device operands, upload once
    lazy = transfer.lazy_fetch_enabled()

    def _alive(fin) -> Tuple[bool, Optional[np.ndarray]]:
        """Verdict of a completed walk: with lazy fetch ONE boolean
        crosses the wire (the round trip the valid path pays); eager
        fetches the full set. Returns ``(alive, final_np_or_None)``;
        a summary-reduction failure records one obs fallback and the
        call degrades to eager for the rest of this walk."""
        nonlocal lazy
        if lazy:
            try:
                a = bool(np.asarray(_jit_any()(fin)))
                obs.count("fetch.lazy")
                return a, None
            except Exception as e:                      # noqa: BLE001
                # fetch the final set FIRST: jax dispatch is async, so
                # a walk error also surfaces at first consumption — a
                # poisoned result propagates here and is NOT recorded
                # as a lazy-fetch failure
                fn = np.asarray(fin)
                obs.engine_fallback("lazy-fetch", type(e).__name__)
                lazy = False
                obs.count("fetch.eager")
                return bool(fn.any()), fn
        fn = np.asarray(fin)
        obs.count("fetch.eager")
        return bool(fn.any()), fn

    ckpts, final = _pipe_walk(host_args, geom, n_fast, interpret, dsegs)
    alive, final_np = _alive(final)              # the ONE round-trip
    if alive:
        # sound: fewer-than-W passes only UNDER-approximate the config
        # set, and emptiness is monotone, so a surviving set certifies
        # linearizability exactly
        if n_fast < W and fetch_R:
            # the surviving set may be an under-approximation when the
            # ladder was capped below W; consumers of R_final (evidence
            # decoding) get the exact set from the W-pass kernel
            _, final = _pipe_walk(host_args, geom, W, interpret, dsegs)
            final_np = None
        if not fetch_R:
            return -1, None
        if final_np is None:
            final_np = np.asarray(final)         # lazy: R consumers pay
        return -1, (final_np > 0.5).T
    if n_fast < W:
        # the fast kernel's verdict may be a false death: decide with
        # the exact W-pass kernel (rare — invalid histories and the
        # occasional deep-chain-dependent valid one)
        ckpts, final = _pipe_walk(host_args, geom, W, interpret, dsegs)
        alive, final_np = _alive(final)
        if alive:
            if not fetch_R:
                return -1, None
            if final_np is None:
                final_np = np.asarray(final)
            return -1, (final_np > 0.5).T
    # dead for real: locate the first empty checkpoint (block starts),
    # then re-walk the preceding block exactly for the knossos-style
    # failing return index
    ckpt_np = _pipe_ckpt_np(ckpts, R_pad // B)   # rare death-only fetch
    occupied = ckpt_np.reshape(ckpt_np.shape[0], -1).any(axis=1)
    first_empty = int(np.argmin(occupied)) if not occupied.all() \
        else ckpt_np.shape[0]
    blk = max(0, first_empty - 1)
    dead = _refine_dead(P, W, M, ret_slot, slot_ops,
                        ckpt_np[blk].T > 0.5, blk * B,
                        min(B, R_real - blk * B))
    return dead, None
