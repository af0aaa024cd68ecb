"""Shared lockstep dispatch core + multi-host seam (ISSUE 19, fast
half): :class:`dispatch_core.DispatchState` placement/window
bookkeeping, the packed-dispatch and :func:`rescue_once`
exactly-one-fallback contracts, :class:`ChunkShard` range math, the
word-packed row codec behind the DCN payload, and the stub-shard
rescue differential — a 2-process shard whose gather dies forces full
local re-derivation, and the verdict/witness must stay bit-identical
to the single-process walk with exactly ONE ``dist-gather`` fallback
recorded. The REAL two-subprocess path is tests/test_dist_chunklock.py
(slow)."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jepsen_tpu import fixtures, models, obs
from jepsen_tpu.checkers import (dispatch_core, reach, reach_chunklock,
                                 reach_word)
from jepsen_tpu.history import pack
from jepsen_tpu.parallel.distributed import ChunkShard, DistGatherError


class _Dev:
    def __init__(self, i):
        self.id = i


class _Prep:
    device = None


def test_dispatch_state_depth_and_round_robin():
    dead = np.full(8, -1, np.int64)
    st = dispatch_core.DispatchState(None, dead)
    assert st.n_dev == 1
    # one walking + PIPE_DEPTH queued
    assert st.depth == dispatch_core.PIPE_DEPTH
    assert st.mesh_info(0) is None

    devs = [_Dev(i) for i in range(3)]
    st = dispatch_core.DispatchState(devs, dead)
    assert st.depth == 3 * (dispatch_core.PIPE_DEPTH + 1) - 1
    prep = _Prep()
    for gi in range(5):
        di, sp = st.place(gi, [gi], prep)
        assert di == gi % 3
        assert prep.device is devs[di]
        assert sp == {"lanes": 1, "device": di}
    assert st.dev_groups == [2, 2, 1]
    info = st.mesh_info(pad_lanes=4)
    assert info["n_devices"] == 3 and info["pad_lanes"] == 4


def test_reach_alias_is_the_shared_core():
    """reach keeps ``_LockstepDispatchState`` as an alias — the keyed
    lockstep lane runs the SAME state machine as chunklock's
    dispatches (no second choreography)."""
    assert reach._LockstepDispatchState is dispatch_core.DispatchState
    assert reach._LOCKSTEP_PIPE_DEPTH == dispatch_core.PIPE_DEPTH


def test_dispatch_packed_dense_retry_records_one_fallback():
    """A packed-wire dispatch failure retries dense ONCE and records
    exactly one fallback — after the dense retry succeeds."""
    seed = (np.arange(64).reshape(8, 8) % 3 == 0).astype(np.float32)
    calls = []

    def run(a, wire):
        calls.append(np.asarray(wire).dtype)
        if np.asarray(wire).dtype == np.uint8:      # the packed wire
            raise RuntimeError("packed decode unsupported")
        return "ok"

    with obs.capture() as cap:
        out = dispatch_core.dispatch_packed(
            run, (np.zeros(4, np.float32),), seed, 100)
    assert out == "ok"
    assert calls == [np.dtype(np.uint8), np.dtype(np.float32)]
    fbs = cap.fallbacks()
    assert len(fbs) == 1
    assert fbs[0]["stage"] == "packed-xfer"
    assert fbs[0]["cause"] == "RuntimeError"
    assert cap.counters.get(
        "engine.fallback.packed-xfer.RuntimeError") == 1
    # both crossings accounted: packed put + the dense re-cross
    assert cap.counters.get("transfer.packed_bytes", 0) > 0


def test_dispatch_packed_persistent_failure_unrecorded():
    """A failure that persists through the dense retry was not the
    packed wire's fault: it propagates with NO fallback record."""
    seed = np.ones((4, 4), np.float32)

    def run(wire):
        raise ValueError("backend down")

    with obs.capture() as cap:
        with pytest.raises(ValueError):
            dispatch_core.dispatch_packed(run, (), seed, 0)
    assert cap.fallbacks() == []


def test_rescue_once_contract():
    with obs.capture() as cap:
        out = dispatch_core.rescue_once("dist-gather", "DistGatherError",
                                        lambda: 42, chunks=3)
    fbs = cap.fallbacks()
    assert out == 42 and len(fbs) == 1
    assert fbs[0]["stage"] == "dist-gather" and fbs[0]["chunks"] == 3
    # a recovery that itself fails propagates unrecorded
    with obs.capture() as cap:
        with pytest.raises(KeyError):
            dispatch_core.rescue_once("dist-gather", "X",
                                      lambda: {}["missing"])
    assert cap.fallbacks() == []


def test_chunk_shard_ranges_partition():
    for C in (1, 2, 5, 7, 8, 64):
        for Pn in (2, 3, 4, 9):
            ranges = [ChunkShard(i, Pn).chunk_range(C)
                      for i in range(Pn)]
            got = []
            for lo, hi in ranges:
                assert 0 <= lo <= hi <= C
                got.extend(range(lo, hi))
            assert got == list(range(C)), (C, Pn, ranges)


def test_pack_rows_round_trip():
    r = np.random.default_rng(3)
    for rows, N in ((1, 32), (5, 31), (4, 33), (3, 257), (0, 64)):
        R = r.integers(0, 2, (rows, N)).astype(bool)
        w = reach_word.pack_rows(R)
        assert w.dtype == np.uint32
        assert w.shape == (rows, -(-N // 32))
        np.testing.assert_array_equal(reach_word.unpack_rows(w, N), R)


# -- the stub-shard rescue differential ---------------------------------

class _DyingShard(ChunkShard):
    """Looks like rank 0 of a 2-process pod whose peer dies at the
    gather: the ONLY blocking dependency on the peer fails, so the
    exact-rescue must re-derive the remote chunks locally."""

    def gather(self, local):
        raise DistGatherError("peer died (injected)")


@pytest.mark.parametrize("corrupt", [False, True])
def test_stub_shard_gather_death_exact_rescue(corrupt):
    model = models.cas_register()
    hh = fixtures.gen_history("cas", n_ops=60, processes=4, seed=11)
    if corrupt:
        hh = fixtures.corrupt(hh, seed=2)
    p = pack(hh)
    ref = reach_chunklock.check_packed(
        model, p, n_chunks=4, suffix=8, e_pad=4, interpret=True,
        process_shard=False)
    with obs.capture() as cap:
        res = reach_chunklock.check_packed(
            model, p, n_chunks=4, suffix=8, e_pad=4, interpret=True,
            process_shard=_DyingShard(0, 2))
    assert res["valid"] == ref["valid"]
    if ref["valid"] is False:
        assert res["dead-event"] == ref["dead-event"]
        assert res["op"] == ref["op"]
    # exactly ONE fallback, recorded after the re-derivation succeeded
    fbs = cap.fallbacks()
    assert len(fbs) == 1
    assert fbs[0]["stage"] == "dist-gather"
    assert fbs[0]["cause"] == "DistGatherError"
    # the remote half of the chunk axis was re-derived locally
    assert res["dist"]["rescued_chunks"] >= 1
    assert cap.counters.get("dist.rescue_chunks", 0) >= 1
    assert cap.counters.get("dist.device_s", 0) > 0


def test_stub_shard_trailing_rank_owns_remainder():
    """Rank 1 of 2 owns the TRAILING chunk range (possibly smaller);
    its rescue re-derives the leading chunks and verdicts still
    match."""
    model = models.cas_register()
    p = pack(fixtures.gen_history("cas", n_ops=55, processes=4,
                                  seed=17))
    ref = reach_chunklock.check_packed(
        model, p, n_chunks=5, suffix=8, e_pad=4, interpret=True,
        process_shard=False)
    with obs.capture() as cap:
        res = reach_chunklock.check_packed(
            model, p, n_chunks=5, suffix=8, e_pad=4, interpret=True,
            process_shard=_DyingShard(1, 2))
    assert res["valid"] == ref["valid"] is True
    assert len(cap.fallbacks()) == 1
    lo, hi = res["dist"]["local_chunks"]
    assert res["dist"]["rescued_chunks"] == 5 - (hi - lo)


def test_autotune_process_count_keying(tmp_path, monkeypatch):
    """Pod winners carry a ``P<n>`` key segment: a winner recorded on
    a 4-process mesh never steers single-host routing, and vice
    versa. Single-process keys keep the historical 3-part format so
    existing tables stay live."""
    from jepsen_tpu.checkers import autotune

    monkeypatch.delenv("JEPSEN_TPU_NO_PERSIST", raising=False)
    monkeypatch.setenv("JEPSEN_TPU_CACHE_DIR", str(tmp_path))
    assert autotune._entry_key("walk", "cpu", "S8-W5-M32-R128", 1) \
        == "walk|cpu|S8-W5-M32-R128"
    assert autotune._entry_key("walk", "cpu", "S8-W5-M32-R128", 4) \
        == "walk|cpu|P4|S8-W5-M32-R128"
    autotune.record("walk", "S8-W5-M32-R128", "word", process_count=4)
    assert autotune.winner("walk", "S8-W5-M32-R128",
                           process_count=4) == "word"
    # the pod winner is invisible single-process (and vice versa)
    assert autotune.winner("walk", "S8-W5-M32-R128",
                           process_count=1) is None
    autotune.record("walk", "S8-W5-M32-R128", "dense",
                    process_count=1)
    assert autotune.winner("walk", "S8-W5-M32-R128",
                           process_count=1) == "dense"
    assert autotune.winner("walk", "S8-W5-M32-R128",
                           process_count=4) == "word"
    # default keying reads the live runtime (single-process here)
    assert autotune.winner("walk", "S8-W5-M32-R128") == "dense"
