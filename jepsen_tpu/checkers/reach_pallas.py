"""Pallas TPU kernel for the dense-reachability returns walk.

The XLA fast path (:func:`jepsen_tpu.checkers.reach._walk_returns`)
executes each return event as ~25 separate tiny fused HLO ops inside a
``lax.while_loop`` — at the headline config (S=8 states, W=5 slots,
M=32 masks) the walk is pure dispatch overhead: every op touches ≤1 KB.
This kernel runs the ENTIRE walk as one ``pallas_call``: the config set
``R`` (laid out ``[M, S]`` f32 0/1) lives in a VMEM scratch register
across a sequential grid; return-slot / pending-op metadata streams in
as SMEM blocks; each fire pass is ONE fused MXU matmul
``R[M, S] @ G_all[S, W·S]`` applying every pending op at once.

Semantics are identical to ``_walk_returns`` (upstream analogue:
``knossos/src/knossos/linear.clj``'s per-event config-set advance):

- per return, monotone Jacobi fire passes run to the between-returns
  fixpoint, detected by popcount stability and capped at W;
- firing slot ``j`` maps configs with bit j clear into their bit-set
  images through ``G = P[slot_ops[r, j]]`` — expressed as static
  half-splits (no scatters/gathers on the mask axis);
- the return projection keeps configs that fired the returning slot and
  clears its bit — a blend of the W static projections by scalar 0/1
  indicator multiplies (Mosaic cannot legalize scalar-predicate vector
  selects);
- an emptied config set at return ``r`` is a linearizability violation;
  the kernel records the first such ``r`` in an SMEM cell (the set
  stays empty from then on — firing and projection preserve emptiness —
  so no early exit is needed and the answer is exact).

The kernel is exact (no fingerprint hashing) like the rest of the
engine. ``interpret=True`` runs it on CPU for differential tests.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu.checkers import transfer


def _gather_G(slot_ops_ref, P_ref, k: int, W: int, O1: int):
    """Concatenate the W pending ops' transition matrices for return ``k``
    into one [S, W·S] operand (slot -1 → the all-zero sentinel row)."""
    import jax.numpy as jnp

    Gs = []
    for jj in range(W):
        o = slot_ops_ref[k * W + jj]
        o = jnp.where(o < 0, O1 - 1, o)
        Gs.append(P_ref[o])                       # [S, S] f32
    return jnp.concatenate(Gs, axis=1)            # [S, W*S]


def _one_fire_pass(R, G_all, W: int, M: int, S: int):
    """One Jacobi fire pass: ONE fused [M,S]@[S,W·S] matmul computes every
    config's image under every slot's op; the per-slot loop then only
    reshuffles halves (VPU). No scatter in Mosaic: rebuild via stacked
    halves. Semantics match ``reach._ret_step``'s einsum."""
    import jax.numpy as jnp

    F = jnp.dot(R, G_all, preferred_element_type=jnp.float32)
    for jj in range(W):
        Fj = F[:, jj * S:(jj + 1) * S]
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, S)
        Fr = Fj.reshape(half, 2, blk, S)
        hi = jnp.maximum(
            Rr[:, 1], (Fr[:, 0] > 0.5).astype(jnp.float32))
        R = jnp.stack([Rr[:, 0], hi], axis=1).reshape(M, S)
    return R


def _fire_and_project(R, G_all, j, W: int, M: int, S: int):
    """One return event on the dense config set ``R`` [M, S] f32:

    - fire passes run to the between-returns fixpoint (fire is monotone,
      so popcount stability == fixpoint), capped at W total (a fire chain
      sets ≥1 new bit per pass). The projected set from the previous
      return is already closed under its still-pending ops, so 2 passes
      almost always suffice — and Mosaic's ``while_loop`` carry costs
      more than a tiny matmul here (measured ~1.5× on the headline
      config), so the first two passes are UNROLLED unconditionally and
      the loop runs only in the rare case the second pass still grew the
      set;
    - projection on the (dynamic) returning slot ``j``: scalar-predicate
      vector selects don't legalize in Mosaic, so blend all W static
      projections with scalar 0/1 indicator multiplies — exactly one is
      hot (or none for j = -1 padding → identity).
    """
    import jax
    import jax.numpy as jnp

    if W <= 2:
        for _ in range(W):                  # W passes ARE the fixpoint
            R = _one_fire_pass(R, G_all, W, M, S)
    else:
        R = _one_fire_pass(R, G_all, W, M, S)
        s1 = jnp.sum(R)
        R = _one_fire_pass(R, G_all, W, M, S)

        def fire_cond(c):
            Rv, prev, it = c
            return jnp.logical_and(it < W, jnp.sum(Rv) > prev)

        def fire_body(c):
            Rv, prev, it = c
            s = jnp.sum(Rv)
            return _one_fire_pass(Rv, G_all, W, M, S), s, it + 1

        R, _, _ = jax.lax.while_loop(fire_cond, fire_body, (R, s1, 2))

    acc = R * (j < 0).astype(jnp.float32)
    for jj in range(W):
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, S)
        taken = Rr[:, 1]
        proj = jnp.stack([taken, jnp.zeros_like(taken)],
                         axis=1).reshape(M, S)
        acc = acc + proj * (j == jj).astype(jnp.float32)
    return acc


def _make_kernel(B: int, W: int, M: int, S: int, O1: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(rlim_ref, ret_slot_ref, slot_ops_ref, R0_ref, P_ref,
               Rout_ref, dead_ref, R_scr, dead_scr):
        step = pl.program_id(0)
        nsteps = pl.num_programs(0)

        @pl.when(step == 0)
        def _init():
            R_scr[:] = R0_ref[:]
            dead_scr[0] = jnp.int32(-1)

        def do_return(k, _):
            r = step * B + k
            j = ret_slot_ref[k]
            G_all = _gather_G(slot_ops_ref, P_ref, k, W, O1)
            R = _fire_and_project(R_scr[:], G_all, j, W, M, S)

            @pl.when(jnp.logical_and(dead_scr[0] < 0,
                                     jnp.logical_and(jnp.sum(R) < 0.5,
                                                     r < rlim_ref[0])))
            def _mark_dead():
                dead_scr[0] = r

            R_scr[:] = R
            return 0

        jax.lax.fori_loop(0, B, do_return, 0)

        @pl.when(step == nsteps - 1)
        def _finish():
            Rout_ref[:] = R_scr[:]
            dead_ref[0] = dead_scr[0]

    return kernel


@functools.cache
def _walk_call(B: int, W: int, M: int, S: int, O1: int, R_pad: int,
               interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _make_kernel(B, W, M, S, O1)
    call = pl.pallas_call(
        kernel,
        grid=(R_pad // B,),
        in_specs=[
            # the real (unpadded) return count, as a runtime scalar so
            # histories of different length share one compiled kernel
            pl.BlockSpec((1,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((B,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            # flat [B*W] — a 2-D SMEM window pads each row to the 1 KB
            # tile and blows the 1 MB SMEM budget
            pl.BlockSpec((B * W,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((M, S), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((O1, S, S), lambda i: (0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((M, S), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1,), lambda i: (0,),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, S), jnp.float32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((M, S), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )

    def run(rlim, ret_slot, slot_ops, R0, P):
        # narrow wire, int32 on device: the upcasts live inside the
        # jitted program so the link carries only the narrow bytes;
        # the R0 seed may arrive bit-packed (8 configs per byte)
        if R0.dtype == jnp.uint8:
            R0 = jnp.unpackbits(R0, count=M * S).reshape(M, S) \
                    .astype(jnp.float32)
        return call(rlim, ret_slot.astype(jnp.int32),
                    slot_ops.astype(jnp.int32), R0, P)

    return jax.jit(run)


_BLOCK = 1024     # XLA tiles 1-D s32 SMEM operands at T(1024); the block
                  # shape must match or Mosaic rejects the layout


def walk_returns(P: np.ndarray, ret_slot: np.ndarray,
                 slot_ops: np.ndarray, R0_sm: np.ndarray, *,
                 interpret: bool = False,
                 fetch_R: bool = True) -> Tuple[int, Optional[np.ndarray]]:
    """Run the full returns walk in one kernel.

    ``P`` f32[O1, S, S] (last row all-zero sentinel); ``ret_slot``
    i32[R]; ``slot_ops`` i32[R, W]; ``R0_sm`` bool[S, M] (the engine's
    native layout). Returns ``(dead, R_final[S, M] bool)`` where
    ``dead`` is the first return index at which the config set emptied,
    or -1 if the history prefix is linearizable. With ``fetch_R=False``
    the final config set is not copied back (``None``) — the verdict
    needs only ``dead``, and each host fetch is a blocking round-trip.
    """
    import jax

    O1, S, _ = P.shape
    R_real = int(ret_slot.shape[0])
    W = int(slot_ops.shape[1])
    M = R0_sm.shape[1]
    from jepsen_tpu.checkers.reach import _bucket

    B = _BLOCK
    # bucket the padded length (8 shapes per octave) so same-sized
    # histories share a compiled kernel; pad rows are cheap identities
    R_pad = max(B, _bucket(-(-R_real // B) * B, B))
    if R_pad != R_real:
        ret_slot = np.pad(ret_slot, (0, R_pad - R_real),
                          constant_values=-1)
        slot_ops = np.pad(slot_ops, ((0, R_pad - R_real), (0, 0)),
                          constant_values=-1)
    call = _walk_call(B, W, M, S, O1, R_pad, interpret)
    # one batched host->device transfer, not five round-trips — on the
    # narrow/bit-packed wire format (in-jit upcasts; round-5 int32/f32
    # with the diet opted out)
    def _dense_args():
        return (
            np.array([R_real], np.int32),
            np.ascontiguousarray(ret_slot, np.int32),
            np.ascontiguousarray(slot_ops.reshape(-1), np.int32),
            np.ascontiguousarray(R0_sm.T, np.float32),
            np.ascontiguousarray(P, np.float32))

    packed = transfer.packed_enabled()
    if packed:
        host_args = (
            np.array([R_real], np.int32),
            np.ascontiguousarray(ret_slot, transfer.idx_dtype(W)),
            np.ascontiguousarray(slot_ops.reshape(-1),
                                 transfer.idx_dtype(O1)),
            transfer.pack_bool(R0_sm.T),
            np.ascontiguousarray(P, np.float32))
    else:
        host_args = _dense_args()
    transfer.count_put(sum(a.nbytes for a in host_args),
                       4 + R_pad * 4 + R_pad * W * 4 + M * S * 4
                       + P.nbytes)
    args = jax.device_put(host_args)
    try:
        R_out, dead = call(*args)
    except Exception as e:                              # noqa: BLE001
        if not packed:
            raise
        # a packed-wire dispatch failed: retry the dense round-5 format
        # (same contract as the other engines); the re-upload's bytes
        # are counted — they really crossed. The ONE fallback record
        # lands only after the dense retry succeeds: a failure that
        # persists dense (backend capability, geometry) was never the
        # packed wire's fault and propagates unrecorded
        host_args = _dense_args()
        transfer.count_put(sum(a.nbytes for a in host_args), 0)
        R_out, dead = call(*jax.device_put(host_args))
        obs.engine_fallback("packed-xfer", type(e).__name__)
    return int(dead[0]), (np.asarray(R_out, bool).T if fetch_R else None)


# -- keyed batch: many independent keys in one kernel ------------------------
#
# The per-key (`jepsen.independent`) hot path. Instead of vmapping the
# walk with every key padded to the longest return stream (the XLA batch
# path), all keys' REAL returns are concatenated into one flat stream
# tagged with key ids; the kernel walks it sequentially, resetting the
# VMEM config set at each key boundary and recording each key's first
# death index into a K-sized SMEM output. Zero padding waste for skewed
# key sizes, one kernel launch total, and exact per-key dead indices
# (the vmapped XLA walk only brackets death within an unroll block).
# All keys share one transition tensor P: history-dependent per-key op
# alphabets are remapped into a union alphabet by the caller
# (``reach._union_alphabet``); only a union too large for the budgets
# falls back to the XLA path.

def _make_keyed_kernel(B: int, W: int, M: int, S: int, O1: int, K: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(ret_slot_ref, slot_ops_ref, key_ref, P_ref,
               dead_ref, R_scr, prev_scr):
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

        def do_return(b, _):
            r = step * B + b
            j = ret_slot_ref[b]
            key = key_ref[b]
            is_real = key >= 0

            @pl.when(jnp.logical_and(is_real, key != prev_scr[0]))
            def _new_key():
                R_scr[:] = R0
                prev_scr[0] = key

            G_all = _gather_G(slot_ops_ref, P_ref, b, W, O1)
            R = _fire_and_project(R_scr[:], G_all, j, W, M, S)

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
                K_pad: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _make_keyed_kernel(B, W, M, S, O1, K_pad)
    call = pl.pallas_call(
        kernel,
        grid=(N_pad // B,),
        in_specs=[
            pl.BlockSpec((B,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((B * W,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((B,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
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
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )

    def run(ret_slot, slot_ops, key_id, P):
        # in-jit upcasts off the narrow wire (see _walk_call.run)
        return call(ret_slot.astype(jnp.int32),
                    slot_ops.astype(jnp.int32),
                    key_id.astype(jnp.int32), P)

    return jax.jit(run)


def walk_returns_keyed(P: np.ndarray, ret_slot: np.ndarray,
                       slot_ops: np.ndarray, key_id: np.ndarray,
                       n_keys: int, M: int, *,
                       interpret: bool = False) -> np.ndarray:
    """Walk the concatenation of ``n_keys`` return streams in one kernel.

    ``ret_slot`` i32[N] / ``slot_ops`` i32[N, W] / ``key_id`` i32[N]
    (non-decreasing, the key owning each return) are the flat
    concatenation of all keys' real returns. Returns ``dead[n_keys]``:
    for each key the FLAT index of the first return at which its config
    set emptied, or -1 if that key's history is linearizable.
    """
    import jax

    from jepsen_tpu.checkers.reach import _bucket

    O1, S, _ = P.shape
    N = int(ret_slot.shape[0])
    W = int(slot_ops.shape[1])
    B = _BLOCK
    N_pad = max(B, _bucket(-(-max(N, 1) // B) * B, B))
    K_pad = max(8, _bucket(n_keys, 8))
    if N_pad != N:
        ret_slot = np.pad(ret_slot, (0, N_pad - N), constant_values=-1)
        slot_ops = np.pad(slot_ops, ((0, N_pad - N), (0, 0)),
                          constant_values=-1)
        key_id = np.pad(key_id, (0, N_pad - N), constant_values=-1)
    call = _keyed_call(B, W, M, S, O1, N_pad, K_pad, interpret)
    def _dense_args():
        return (
            np.ascontiguousarray(ret_slot, np.int32),
            np.ascontiguousarray(slot_ops.reshape(-1), np.int32),
            np.ascontiguousarray(key_id, np.int32),
            np.ascontiguousarray(P, np.float32))

    packed = transfer.packed_enabled()
    if packed:
        host_args = (
            np.ascontiguousarray(ret_slot, transfer.idx_dtype(W)),
            np.ascontiguousarray(slot_ops.reshape(-1),
                                 transfer.idx_dtype(O1)),
            np.ascontiguousarray(key_id, transfer.idx_dtype(K_pad)),
            np.ascontiguousarray(P, np.float32))
    else:
        host_args = _dense_args()
    transfer.count_put(sum(a.nbytes for a in host_args),
                       N_pad * 4 + N_pad * W * 4 + N_pad * 4 + P.nbytes)
    args = jax.device_put(host_args)
    try:
        (dead,) = call(*args)
    except Exception as e:                              # noqa: BLE001
        if not packed:
            raise
        # same packed-wire contract as walk_returns: dense retry with
        # re-upload bytes counted, ONE fallback record only once the
        # dense retry succeeds (a dense failure too means the packed
        # wire was not at fault — propagate unrecorded)
        host_args = _dense_args()
        transfer.count_put(sum(a.nbytes for a in host_args), 0)
        (dead,) = call(*jax.device_put(host_args))
        obs.engine_fallback("packed-xfer", type(e).__name__)
    return np.asarray(dead)[:n_keys]
