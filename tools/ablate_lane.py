"""On-chip ablation harness for the single-history lane kernel.

Builds the cas-100k operand set once, then times kernel VARIANTS by
dispatch slope (K queued dispatches + 1 fetch, minus 1 dispatch +
fetch; how the slope compares with ``block_until_ready`` is unmeasured
on the chip). Used to drive the round-3 kernel redesign.

Usage: python tools/ablate_lane.py [--ops N] [--variants a,b,...]
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_BLOCK = 1024


def _probe(run, args, K: int = 6):
    import numpy as np
    _ = np.asarray(run(*args)[1])               # warm/compile
    t0 = time.monotonic()
    _ = np.asarray(run(*args)[1])
    one_s = time.monotonic() - t0
    t0 = time.monotonic()
    outs = [run(*args) for _ in range(K)]
    _ = np.asarray(outs[-1][1])
    many_s = time.monotonic() - t0
    return max(0.0, (many_s - one_s) / (K - 1))


# -- pass bodies -------------------------------------------------------------

def _fire_bool(R, G_all, W, M, S):
    """Round-2 pass: boolean compare+cast, serial max merge."""
    import jax.numpy as jnp
    F = jnp.dot(R, G_all, preferred_element_type=jnp.float32)
    for jj in range(W):
        Fj = F[:, jj * S:(jj + 1) * S]
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, S)
        Fr = Fj.reshape(half, 2, blk, S)
        hi = jnp.maximum(Rr[:, 1], (Fr[:, 0] > 0.5).astype(jnp.float32))
        R = jnp.stack([Rr[:, 0], hi], axis=1).reshape(M, S)
    return R


def _fire_counts_tree(R, G_all, W, M, S):
    """Counts, balanced add tree."""
    import jax.numpy as jnp
    F = jnp.dot(R, G_all, preferred_element_type=jnp.float32)
    vals = [R]
    for jj in range(W):
        Fj = F[:, jj * S:(jj + 1) * S]
        half, blk = M >> (jj + 1), 1 << jj
        lo = Fj.reshape(half, 2, blk, S)[:, 0]
        vals.append(jnp.stack([jnp.zeros_like(lo), lo],
                              axis=1).reshape(M, S))
    while len(vals) > 1:
        nxt = [vals[i] + vals[i + 1] for i in range(0, len(vals) - 1, 2)]
        if len(vals) % 2:
            nxt.append(vals[-1])
        vals = nxt
    return vals[0]


def _fire_counts_gs(R, G_all, W, M, S):
    """Counts, Gauss-Seidel-shaped serial merge (add replaces max,
    compare+cast dropped — minimal diff from round 2)."""
    import jax.numpy as jnp
    F = jnp.dot(R, G_all, preferred_element_type=jnp.float32)
    for jj in range(W):
        Fj = F[:, jj * S:(jj + 1) * S]
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, S)
        Fr = Fj.reshape(half, 2, blk, S)
        hi = Rr[:, 1] + Fr[:, 0]
        R = jnp.stack([Rr[:, 0], hi], axis=1).reshape(M, S)
    return R


def _fire_bool_rev(R, G_all, W, M, S):
    """Round-2 pass with the Gauss-Seidel slot sweep REVERSED: chains
    that linearize in descending slot order complete in one pass."""
    import jax.numpy as jnp
    F = jnp.dot(R, G_all, preferred_element_type=jnp.float32)
    for jj in reversed(range(W)):
        Fj = F[:, jj * S:(jj + 1) * S]
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, S)
        Fr = Fj.reshape(half, 2, blk, S)
        hi = jnp.maximum(Rr[:, 1], (Fr[:, 0] > 0.5).astype(jnp.float32))
        R = jnp.stack([Rr[:, 0], hi], axis=1).reshape(M, S)
    return R


def _fire_maxnc(R, G_all, W, M, S):
    """Round-2 structure with the compare+cast dropped: max against the
    raw f32 contraction (values grow ≤8x per pass; one min(R,1) clamp
    per return restores the 0/1 scale — zero/nonzero is preserved)."""
    import jax.numpy as jnp
    F = jnp.dot(R, G_all, preferred_element_type=jnp.float32)
    for jj in range(W):
        Fj = F[:, jj * S:(jj + 1) * S]
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, S)
        Fr = Fj.reshape(half, 2, blk, S)
        hi = jnp.maximum(Rr[:, 1], Fr[:, 0])
        R = jnp.stack([Rr[:, 0], hi], axis=1).reshape(M, S)
    return R


# -- projection bodies -------------------------------------------------------

def _proj_blend(R, j, W, M, S, counts: bool):
    import jax.numpy as jnp
    acc = R * (j < 0).astype(jnp.float32)
    for jj in range(W):
        half, blk = M >> (jj + 1), 1 << jj
        Rr = R.reshape(half, 2, blk, S)
        taken = Rr[:, 1]
        p = jnp.stack([taken, jnp.zeros_like(taken)],
                      axis=1).reshape(M, S)
        acc = acc + p * (j == jj).astype(jnp.float32)
    return jnp.minimum(acc, 1.0) if counts else acc


def _proj_table_np(W, M):
    PJ = np.zeros((W + 1, M, M), np.float32)
    m = np.arange(M)
    for j in range(W):
        clear = (m & (1 << j)) == 0
        PJ[j, m[clear], (m | (1 << j))[clear]] = 1.0
    PJ[W] = np.eye(M, dtype=np.float32)
    return PJ


# -- kernel factory ----------------------------------------------------------

def make_call(B, W, M, S, O1, R_pad, n_pass, fire, proj_kind,
              counts, unroll=1, cgate=0):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jepsen_tpu.checkers.reach_pallas import _gather_G

    n_blocks = R_pad // B
    use_pj = proj_kind == "matmul"

    def kernel(ret_slot_ref, slot_ops_ref, extra_ref, P_ref, PJ_ref,
               R0_ref, ckpt_ref, final_ref, R_scr, G_scr, PJ_scr):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            R_scr[:] = R0_ref[:]

        ckpt_ref[0] = R_scr[:]
        G_scr[0] = _gather_G(slot_ops_ref, P_ref, 0, W, O1)
        if use_pj:
            j0 = ret_slot_ref[0]
            PJ_scr[0] = PJ_ref[jnp.where(j0 < 0, W, j0)]

        def one(k, R):
            G_all = G_scr[k % 2]
            if use_pj:
                PJk = PJ_scr[k % 2]
            kn = jnp.minimum(k + 1, B - 1)
            G_scr[(k + 1) % 2] = _gather_G(slot_ops_ref, P_ref, kn, W, O1)
            if use_pj:
                jn = ret_slot_ref[kn]
                PJ_scr[(k + 1) % 2] = PJ_ref[jnp.where(jn < 0, W, jn)]
            fires = fire if isinstance(fire, tuple) else (fire,)
            for _p in range(n_pass):
                R = fires[_p % len(fires)](R, G_all, W, M, S)
            if cgate:
                # deep-chain returns (pending count c > threshold) run
                # their remaining exact passes under untaken-free
                # pl.whens: R_scr carries the result across gates
                R_scr[:] = R
                off = n_pass
                for g in cgate:
                    def _deep(off=off, g=g):
                        Rd = R_scr[:]
                        for _p in range(g):
                            Rd = fires[(off + _p) % len(fires)](
                                Rd, G_all, W, M, S)
                        R_scr[:] = Rd
                    pl.when(extra_ref[k] > off)(_deep)
                    off += g
                R = R_scr[:]
            if use_pj:
                R = jnp.dot(PJk, R, preferred_element_type=jnp.float32)
                if counts:
                    R = jnp.minimum(R, 1.0)
            else:
                R = _proj_blend(R, ret_slot_ref[k], W, M, S, counts)
            return R

        def do_return(k, _):
            R = R_scr[:]
            for u in range(unroll):
                R = one(k * unroll + u, R)
            R_scr[:] = R
            return 0

        jax.lax.fori_loop(0, B // unroll, do_return, 0)

        @pl.when(step == n_blocks - 1)
        def _finish():
            final_ref[:] = R_scr[:]

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
            pl.BlockSpec((W + 1, M, M), lambda i: (0, 0, 0),
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
            pltpu.VMEM((2, M, M), jnp.float32),
        ],
    )

    def run(ret_slot, slot_ops, P, PJ, R0):
        so = slot_ops.astype(jnp.int32)
        extra = (so.reshape(R_pad, W) >= 0).sum(axis=1)
        return call(ret_slot.astype(jnp.int32), so,
                    extra.astype(jnp.int32), P, PJ, R0)

    return jax.jit(run)


def make_call_stream(B, W, M, S, O1, R_pad, n_pass, fire, counts,
                     g_dtype="float32"):
    """Streamed-G variant: the per-return fire operand is pre-gathered
    for ALL returns by one XLA gather on device (HBM-resident
    ``[R_pad, S, W*S]``) and streamed through the pallas pipeline —
    the in-kernel gather (and its SMEM scalar reads) disappear; the
    DMA engine does the fetch while the MXU chain runs."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_blocks = R_pad // B

    def kernel(ret_slot_ref, G_ref, R0_ref, ckpt_ref, final_ref, R_scr):
        step = pl.program_id(0)

        @pl.when(step == 0)
        def _init():
            R_scr[:] = R0_ref[:]

        ckpt_ref[0] = R_scr[:]

        def do_return(k, _):
            G_all = G_ref[k]
            if g_dtype != "float32":
                G_all = G_all.astype(jnp.float32)
            R = R_scr[:]
            for _p in range(n_pass):
                R = fire(R, G_all, W, M, S)
            R_scr[:] = _proj_blend(R, ret_slot_ref[k], W, M, S, counts)
            return 0

        jax.lax.fori_loop(0, B, do_return, 0)

        @pl.when(step == n_blocks - 1)
        def _finish():
            final_ref[:] = R_scr[:]

    call = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((B, S, W * S), lambda i: (i, 0, 0),
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
        ],
    )

    def run(ret_slot, slot_ops, P, PJ, R0):
        so = slot_ops.astype(jnp.int32).reshape(R_pad, W)
        o = jnp.where(so < 0, O1 - 1, so)
        G = P[o]                                   # [R_pad, W, S, S]
        G = jnp.transpose(G, (0, 2, 1, 3)).reshape(R_pad, S, W * S)
        G = G.astype(g_dtype)
        return call(ret_slot.astype(jnp.int32), G, R0)

    return jax.jit(run)


VARIANTS = {
    # name: (fire, proj, counts, unroll, n_pass or None=min(W,5))
    "v2-bool-blend": (_fire_bool, "blend", False, 1, None),
    "cnt-tree-blend": (_fire_counts_tree, "blend", True, 1, None),
    "maxnc-blend": (_fire_maxnc, "blend", True, 1, None),
    "bool-matmulproj": (_fire_bool, "matmul", False, 1, None),
    "bool-stream": (_fire_bool, "stream", False, 1, None),
    "maxnc-stream": (_fire_maxnc, "stream", True, 1, None),
    "bool-stream-i8": (_fire_bool, "stream-i8", False, 1, None),
    "v2-p4": (_fire_bool, "blend", False, 1, 4),
    "v2-p3": (_fire_bool, "blend", False, 1, 3),
    "v2-p2": (_fire_bool, "blend", False, 1, 2),
    "alt-p2": ((_fire_bool, _fire_bool_rev), "blend", False, 1, 2),
    "alt-p3": ((_fire_bool, _fire_bool_rev), "blend", False, 1, 3),
    "alt-p4": ((_fire_bool, _fire_bool_rev), "blend", False, 1, 4),
    # exact per-return pass gating: pending count c_r bounds closure
    # depth, so n_pass unconditional passes + (5 - n_pass) passes under
    # an untaken-free pl.when for the rare c_r > n_pass returns
    "cgate4+1": (_fire_bool, "blend", False, 1, 4, (1,)),
    "cgate3+2": (_fire_bool, "blend", False, 1, 3, (2,)),
    "cgate2+3": (_fire_bool, "blend", False, 1, 2, (3,)),
    "cgate3+1+1": (_fire_bool, "blend", False, 1, 3, (1, 1)),
    "cgate2+1+1+1": (_fire_bool, "blend", False, 1, 2, (1, 1, 1)),
    "cgate2+2+1": (_fire_bool, "blend", False, 1, 2, (2, 1)),
    "cgate1+1+1+1+1": (_fire_bool, "blend", False, 1, 1, (1, 1, 1, 1)),
    "cgate-ladder-u2": (_fire_bool, "blend", False, 2, 1, (1, 1, 1, 1)),
    "cgate-ladder-alt": ((_fire_bool, _fire_bool_rev), "blend", False, 1,
                         1, (1, 1, 1, 1)),
}


def body_sweep(ops: int, repeat: int, record: bool) -> int:
    """Post-hoc KERNEL-BODY sweep (any backend, incl. XLA:CPU): the
    word-packed walk (``reach_word``) vs the dense einsum walk on one
    generated cas history, verdict-asserted identical, winner
    PERSISTED as the autotune ``walk`` entry route selection
    (``reach.check_packed``) consults. The Pallas variant ladder
    below stays the on-chip microscope; this is the cross-body
    decision the table exists for."""
    import json as _json

    import numpy as np

    from jepsen_tpu import fixtures, models
    from jepsen_tpu.checkers import autotune, events as ev
    from jepsen_tpu.checkers import reach, reach_word
    from jepsen_tpu.history import pack

    hist = fixtures.gen_history("cas", n_ops=ops, processes=5,
                                seed=42)
    model = models.cas_register()
    packed = pack(hist)
    memo, stream, _T, S_pad, M = reach._prep(
        model, packed, max_states=100_000, max_slots=20,
        max_dense=1 << 22)
    W = max(stream.W, 1)
    rs = ev.returns_view(stream)
    n = rs.n_returns

    def _one(body: str):
        import os as _os
        env = "JEPSEN_TPU_WORD_POSTHOC"
        no_word = "JEPSEN_TPU_NO_WORD_WALK"
        old = {k: _os.environ.pop(k, None) for k in (env, no_word)}
        try:
            if body == "word":
                _os.environ[env] = "1"
            else:
                _os.environ[no_word] = "1"
            res = reach.check_packed(model, packed)   # warm
            best = float("inf")
            for _ in range(max(1, repeat)):
                t0 = time.monotonic()
                res = reach.check_packed(model, packed)
                best = min(best, time.monotonic() - t0)
            return res, best
        finally:
            for k, v in old.items():
                _os.environ.pop(k, None)
                if v is not None:
                    _os.environ[k] = v

    res_w, t_word = _one("word")
    res_d, t_dense = _one("dense")
    assert res_w["valid"] == res_d["valid"], (res_w, res_d)
    winner = "word" if t_word <= t_dense else "dense"
    row = {"geometry": {"S": memo.n_states, "W": W, "M": M,
                        "returns": int(n)},
           "word_s": round(t_word, 4), "dense_s": round(t_dense, 4),
           "winner": winner,
           "speedup": round(t_dense / max(t_word, 1e-9), 2),
           "word_engine": res_w.get("engine"),
           "dense_engine": res_d.get("engine")}
    if record:
        row["recorded"] = autotune.record(
            "walk", autotune.walk_key(memo.n_states, W, M, n), winner,
            metric=n / max(min(t_word, t_dense), 1e-9),
            detail={"word_s": row["word_s"],
                    "dense_s": row["dense_s"]})
    print(_json.dumps(row), flush=True)
    return 0


def _pipe_drive(model, batches, K: int):
    """Process ``batches`` through the serve-lane window discipline at
    in-flight depth K: stage batch b+1 while b walks, collect ready
    predecessors, block on the oldest at a full window. K=1 is the
    blocking degenerate (``check_many`` per batch) — the bit-identity
    reference. Returns (results per batch, wall seconds)."""
    from collections import deque

    from jepsen_tpu.checkers import reach

    os.environ["JEPSEN_TPU_PIPE_K"] = str(K)
    try:
        t0 = time.monotonic()
        out = [None] * len(batches)
        window: deque = deque()
        for bi, b in enumerate(batches):
            st = reach.stage_check_many(model, b) if K > 1 else None
            if st is None:
                while window:           # FIFO: drain before blocking
                    i, hd = window.popleft()
                    out[i] = hd.collect()
                out[bi] = reach.check_many(model, b)
                continue
            window.append((bi, st))
            while window and window[0][1].ready():
                i, hd = window.popleft()
                out[i] = hd.collect()
            while len(window) >= K:
                i, hd = window.popleft()
                out[i] = hd.collect()
        while window:
            i, hd = window.popleft()
            out[i] = hd.collect()
        return out, time.monotonic() - t0
    finally:
        os.environ.pop("JEPSEN_TPU_PIPE_K", None)


def pipeline_sweep(repeat: int, record: bool) -> int:
    """ISSUE 20 satellite: measure the serve-lane in-flight depth
    K ∈ {1,2,4,8} per geometry bucket with the REAL stage/collect
    protocol (``reach.stage_check_many`` → window → collect), assert
    every depth's verdicts bit-identical to the K=1 blocking
    reference, and persist winners in the autotune table — the
    per-bucket detail rows plus the aggregate ``pipeline|serve``
    entry :func:`dispatch_core.pipeline_k` consults (staleness-guarded
    like every other entry: a winner measured under another XLA is
    ignored at lookup)."""
    import json as _json

    from jepsen_tpu import fixtures, models
    from jepsen_tpu.checkers import autotune, events as ev
    from jepsen_tpu.checkers import reach
    from jepsen_tpu.history import pack

    model = models.cas_register()
    ks = (1, 2, 4, 8)
    overall: dict = {}
    # geometry buckets: return-count and slot-width vary with history
    # length and process count (S is the model's)
    shapes = [(240, 3), (900, 4), (2400, 5)]
    for n_ops, procs in shapes:
        batches = [[pack(fixtures.gen_history(
            "cas", n_ops=n_ops + 40 * j, processes=procs,
            seed=17 * bi + j))
            for j in range(4)] for bi in range(6)]
        memo, stream, _T, _S_pad, M = reach._prep(
            model, batches[0][0], max_states=100_000, max_slots=20,
            max_dense=1 << 22)
        W = max(stream.W, 1)
        rets = ev.returns_view(stream).n_returns
        key = autotune.walk_key(memo.n_states, W, M, rets)
        ref, _ = _pipe_drive(model, batches, 1)       # warm + reference
        walls = {}
        for K in ks:
            best = float("inf")
            for _ in range(max(1, repeat)):
                out, wall = _pipe_drive(model, batches, K)
                for rb, ob in zip(ref, out):
                    for r, o in zip(rb, ob):
                        assert r["valid"] == o["valid"], (K, r, o)
                best = min(best, wall)
            walls[K] = round(best, 4)
        bestK = min(ks, key=lambda K: walls[K])
        row = {"bucket": key, "walls_s": {str(K): walls[K] for K in ks},
               "winner_k": bestK,
               "speedup_vs_k1": round(
                   walls[1] / max(walls[bestK], 1e-9), 2)}
        if record:
            row["recorded"] = autotune.record(
                "pipeline", key, str(bestK),
                metric=1.0 / max(walls[bestK], 1e-9),
                detail={"walls_s": row["walls_s"]})
        overall[key] = (bestK, walls[1] / max(walls[bestK], 1e-9))
        print(_json.dumps(row), flush=True)
    # the aggregate serve-lane entry pipeline_k("serve") consults:
    # the depth that wins the most buckets (speedup breaks ties)
    votes: dict = {}
    for k, gain in overall.values():
        n, g = votes.get(k, (0, 0.0))
        votes[k] = (n + 1, g + gain)
    serve_k = max(votes, key=lambda k: votes[k])
    out = {"bucket": "serve", "winner_k": serve_k,
           "buckets": {b: k for b, (k, _g) in overall.items()}}
    if record:
        out["recorded"] = autotune.record(
            "pipeline", "serve", str(serve_k),
            metric=sum(g for _n, g in votes.values()),
            detail={"votes": {str(k): n for k, (n, _g)
                              in votes.items()}})
    print(_json.dumps(out), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=100_000)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--repeat", type=int, default=2)
    ap.add_argument("--bodies", action="store_true",
                    help="sweep the word-packed vs dense post-hoc "
                         "kernel BODIES (any backend) and persist "
                         "the winner in the autotune table instead "
                         "of running the Pallas variant ladder")
    ap.add_argument("--pipeline", action="store_true",
                    help="sweep the serve-lane in-flight depth "
                         "K in {1,2,4,8} per geometry bucket over the "
                         "real stage/collect protocol and persist "
                         "winners (kind 'pipeline') in the autotune "
                         "table")
    ap.add_argument("--no-record", action="store_true",
                    help="with --bodies/--pipeline: measure only, do "
                         "not write the autotune table")
    args = ap.parse_args()
    if args.pipeline:
        return pipeline_sweep(args.repeat, record=not args.no_record)
    if args.bodies:
        return body_sweep(args.ops, args.repeat,
                          record=not args.no_record)

    import jax
    from jepsen_tpu import fixtures, models
    from jepsen_tpu.history import pack
    from jepsen_tpu.checkers import events as ev
    from jepsen_tpu.checkers import reach, reach_lane

    hist = fixtures.gen_history("cas", n_ops=args.ops, processes=5,
                                seed=42)
    model = models.cas_register()
    packed = pack(hist)
    memo, stream, _T, S, M = reach._prep(
        model, packed, max_states=100_000, max_slots=20,
        max_dense=1 << 22)
    rs = ev.returns_view(stream)
    P_np = reach._build_P(memo, S)
    R0 = np.zeros((S, M), bool)
    R0[0, 0] = True
    geom, _, _, host_args = reach_lane.pack_operands(
        P_np, rs.ret_slot, rs.slot_ops, R0)
    B, W, M, S, O1, R_pad = geom
    R_real = int(rs.ret_slot.shape[0])
    print(f"geometry B={B} W={W} M={M} S={S} O1={O1} R_pad={R_pad} "
          f"returns={R_real}")
    # pack_operands layout (round 4): (ret_slot, slot_ops, P, R0) —
    # pend is derived on device. Insert the projection table the
    # matmul ablation variants expect between slot_ops and P.
    ret_slot_h, slot_ops_h, P_h, R0_h = host_args
    if R0_h.dtype == np.uint8:
        # round-6 diet: pack_operands bit-packs the seed by default;
        # the ablation kernels predate the in-jit unpack, so
        # re-materialize the dense f32 seed they expect
        from jepsen_tpu.checkers import transfer
        R0_h = transfer.unpack_bool_host(R0_h, M * S) \
            .reshape(M, S).astype(np.float32)
    host_args = (ret_slot_h, slot_ops_h, P_h,
                 _proj_table_np(W, M), R0_h)
    dargs = jax.device_put(host_args)
    names = args.variants.split(",")
    runs = {}
    for name in names:
        spec = VARIANTS[name]
        fire, proj, counts, unroll, np_ = spec[:5]
        cgate = spec[5] if len(spec) > 5 else 0
        np_ = min(W, 5) if np_ is None else np_
        try:
            if proj == "stream":
                runs[name] = make_call_stream(B, W, M, S, O1, R_pad,
                                              np_, fire, counts)
            elif proj == "stream-i8":
                runs[name] = make_call_stream(B, W, M, S, O1, R_pad,
                                              np_, fire, counts,
                                              g_dtype="int8")
            else:
                runs[name] = make_call(B, W, M, S, O1, R_pad,
                                       np_, fire, proj, counts,
                                       unroll, cgate)
        except Exception as e:                          # noqa: BLE001
            print(f"{name:22s} BUILD FAILED: {type(e).__name__}: "
                  f"{str(e)[:120]}")
    # interleaved rounds so host/chip drift hits every variant alike
    best = {n: float("inf") for n in runs}
    for _ in range(args.repeat):
        for name, run in runs.items():
            try:
                best[name] = min(best[name], _probe(run, dargs))
            except Exception as e:                      # noqa: BLE001
                print(f"{name:22s} RUN FAILED: {type(e).__name__}: "
                      f"{str(e)[:120]}")
                best[name] = float("nan")
                runs[name] = None
        runs = {n: r for n, r in runs.items() if r is not None}
    ref_final = None
    for name in names:
        if name not in best or best[name] != best[name]:
            continue
        alive = False
        if name in runs:
            final = np.asarray(runs[name](*dargs)[1]) > 0
            alive = bool(final.any())
            if ref_final is None:
                ref_final = final
            agree = bool((final == ref_final).all())
        else:
            agree = False
        print(f"{name:22s} {best[name]*1e3:8.1f} ms "
              f"{best[name]/max(R_real,1)*1e9:7.0f} ns/ret  "
              f"match={agree} alive={alive}")


if __name__ == "__main__":
    main()
