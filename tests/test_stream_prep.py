"""Differential + fallback tests for the keyed lockstep lane's
streaming prep→dispatch scheduler: while group 0 walks on device, a
background prep thread packs groups 1..G and feeds the dispatcher
through a bounded queue. Verdicts and dead indices must be
bit-identical to the per-key sequential path across ragged bucket
mixes, a one-group batch streams like any other, and a prep-thread
exception hands the batch to the next lane with exactly one record in
the obs ledger."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jepsen_tpu import fixtures, models, obs
from jepsen_tpu.checkers import preproc_native, reach, reach_batch
from jepsen_tpu.history import pack

needs_native = pytest.mark.skipif(
    not preproc_native.available(),
    reason="native preprocessing library unavailable")


def _force_stream(monkeypatch):
    """Open the lockstep gates on CPU with the batch kernel in
    interpret mode (the interpret DEFAULT flag reaches the streaming
    scheduler, which never threads an interpret argument), and shrink
    the planner's floor so small mixes split into several groups."""
    monkeypatch.setattr(reach, "_use_pallas", lambda: True)
    monkeypatch.setattr(reach, "_PALLAS_MIN_RETURNS", 0)
    monkeypatch.setattr(reach_batch, "_INTERPRET_DEFAULT", True)
    monkeypatch.setattr(reach_batch, "_adaptive_block", lambda H, W: 64)


def _ragged_packs(lens, corrupt=(), crash_p=0.0, base_seed=7000):
    packs = []
    for i, n in enumerate(lens):
        h = fixtures.gen_history("cas", n_ops=n, processes=3,
                                 seed=base_seed + i, crash_p=crash_p)
        if i in corrupt:
            h = fixtures.corrupt(h, seed=i)
        packs.append(pack(h))
    return packs


@needs_native
def test_streaming_matches_sync_and_sequential(monkeypatch):
    """Ragged mix spanning several buckets: streaming verdicts, dead
    events, and witness ops bit-identical to the per-key sequential
    path."""
    lens = [220, 30, 90, 250, 45, 60, 150, 35, 40, 70]
    packs = _ragged_packs(lens, corrupt={0, 6})
    model = models.cas_register()
    refs = [reach.check_packed(model, p) for p in packs]
    _force_stream(monkeypatch)
    diag = {}
    with obs.capture() as cap:
        res = reach.check_many(model, packs, diag=diag)
    assert all(r["engine"] == "reach-lockstep" for r in res)
    assert diag["prep"]["mode"] == "stream"
    assert diag["prep"]["groups"] >= 2          # genuinely streamed
    assert diag["prep"]["wall_s"] > 0
    assert not [r for r in cap.fallbacks()
                if r["stage"] == "stream-prep"]
    n_bad = 0
    for i, (a, r) in enumerate(zip(res, refs)):
        assert a["valid"] == r["valid"], f"key {i}"
        if a["valid"] is False:
            n_bad += 1
            assert a["dead-event"] == r["dead-event"], f"key {i}"
            assert a["op"] == r["op"], f"key {i}"
            assert a.get("final-configs"), f"key {i} missing witness"
    assert n_bad >= 1                           # the corruptor worked


@needs_native
def test_streaming_check_batch_matches_sequential(monkeypatch):
    """The same pipeline behind reach.check_batch (several complete
    histories), including crashed ops riding through the union route."""
    # crash_p kept low: crashed ops pin slots forever, and W grows
    # past the dense fast-path budget near ~10 crashes in one key
    lens = [200, 40, 90, 120, 45, 60]
    packs = _ragged_packs(lens, corrupt={3}, crash_p=0.02,
                          base_seed=8100)
    model = models.cas_register()
    refs = [reach.check_packed(model, p) for p in packs]
    _force_stream(monkeypatch)
    diag = {}
    res = reach.check_batch(model, packs, diag=diag)
    assert diag["prep"]["mode"] == "stream"
    for i, (a, r) in enumerate(zip(res, refs)):
        assert a["engine"] == "reach-lockstep", f"key {i}"
        assert a["valid"] == r["valid"], f"key {i}"
        if a["valid"] is False:
            assert a["dead-event"] == r["dead-event"], f"key {i}"


@needs_native
def test_prep_thread_exception_falls_back_exactly_once(monkeypatch):
    """A prep-thread exception drains the queue and hands the batch to
    the next lane: exactly ONE stream-prep fallback in the obs ledger,
    the next lane's verdicts and dead events equal the per-key path's,
    and the producer thread can never leave the scheduler deadlocked
    on a full queue."""
    lens = [180, 40, 90, 60, 45, 35]
    packs = _ragged_packs(lens, corrupt={2}, base_seed=9200)
    model = models.cas_register()
    refs = [reach.check_packed(model, p) for p in packs]
    _force_stream(monkeypatch)
    orig = reach._union_pack_group
    calls = {"n": 0}

    def boom(sa, sel, max_slots):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("forced prep failure")
        return orig(sa, sel, max_slots)

    monkeypatch.setattr(reach, "_union_pack_group", boom)
    with obs.capture() as cap:
        res = reach.check_many(model, packs)
    falls = [r for r in cap.fallbacks() if r["stage"] == "stream-prep"]
    assert len(falls) == 1
    assert falls[0]["cause"] == "RuntimeError"
    # the next lane answered the whole batch: nothing was assembled
    # from a half-streamed one
    assert not any(r["engine"] == "reach-lockstep" for r in res)
    for i, (a, r) in enumerate(zip(res, refs)):
        assert a["valid"] == r["valid"], f"key {i}"
        if a["valid"] is False:
            assert a["dead-event"] == r["dead-event"], f"key {i}"


@needs_native
def test_single_group_batch_declines_streaming(monkeypatch):
    """A batch that packs into ONE dispatch group goes through the
    same prep thread and queue as any other: it streams as one group,
    with no fallback record (the name is historical: such a batch once
    declined to a second scheduler)."""
    monkeypatch.setattr(reach, "_use_pallas", lambda: True)
    monkeypatch.setattr(reach, "_PALLAS_MIN_RETURNS", 0)
    monkeypatch.setattr(reach_batch, "_INTERPRET_DEFAULT", True)
    packs = _ragged_packs([60, 45, 50], base_seed=9900)
    model = models.cas_register()
    diag = {}
    with obs.capture() as cap:
        res = reach.check_many(model, packs, diag=diag)
    assert all(r["engine"] == "reach-lockstep" for r in res)
    assert diag["prep"]["mode"] == "stream"
    assert diag["prep"]["groups"] == 1
    assert not [r for r in cap.fallbacks()
                if r["stage"] == "stream-prep"]


@needs_native
def test_union_pack_group_subset_matches_full():
    """Stage B over a subset of the live axis produces exactly the
    rows of the full build (per-key streams are independent) — the
    invariant that makes per-group packing safe."""
    packs = _ragged_packs([80, 50, 65, 40], base_seed=4400)
    model = models.cas_register()
    live = list(range(len(packs)))
    sa = reach._union_stage_a(model, packs, live, 100_000)
    assert sa is not None
    full = reach._union_pack_group(sa, live, 20)
    assert full is not None
    f_ret, f_ops, f_W, f_R, f_off, _ = full
    sub = reach._union_pack_group(sa, [2, 0], 20)
    assert sub is not None
    s_ret, s_ops, s_W, s_R, s_off, _ = sub
    assert int(s_R[0]) == int(f_R[2]) and int(s_R[1]) == int(f_R[0])
    np.testing.assert_array_equal(
        s_ret[s_off[0]:s_off[1]], f_ret[f_off[2]:f_off[3]])
    np.testing.assert_array_equal(
        s_ret[s_off[1]:s_off[2]], f_ret[f_off[0]:f_off[1]])
    W = min(s_ops.shape[1], f_ops.shape[1])
    np.testing.assert_array_equal(
        s_ops[s_off[0]:s_off[1], :W], f_ops[f_off[2]:f_off[3], :W])
