"""Differential + fallback tests for the device-sharded lockstep lane
(ISSUE 4 tentpole): ``check_many``/``check_batch`` with ``devices>1``
route through mesh-lockstep — dispatch groups split into per-device
lane blocks and multi-queued so N chips walk concurrently — with
verdicts bit-identical to the single-device lockstep scheduler and the
per-key sequential path. A mesh dispatch failure falls back to the
SINGLE-DEVICE lockstep lane exactly once (never silently the keyed
kernel); ``JEPSEN_TPU_NO_MESH_LOCKSTEP=1`` opts out to the keyed
mesh-union lane."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jepsen_tpu import fixtures, models, obs
from jepsen_tpu.checkers import preproc_native, reach, reach_batch
from jepsen_tpu.history import pack

needs_native = pytest.mark.skipif(
    not preproc_native.available(),
    reason="native preprocessing library unavailable")
needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs a >=4-device (virtual CPU) mesh")


def _force_mesh(monkeypatch):
    """Open the lockstep gates on CPU with the batch kernel in
    interpret mode and a small planner floor (several groups per
    batch), and make sure the mesh lane is not env-disabled."""
    monkeypatch.setattr(reach, "_use_pallas", lambda: True)
    monkeypatch.setattr(reach, "_PALLAS_MIN_RETURNS", 0)
    monkeypatch.setattr(reach_batch, "_INTERPRET_DEFAULT", True)
    monkeypatch.setattr(reach_batch, "_adaptive_block", lambda H, W: 64)
    monkeypatch.delenv("JEPSEN_TPU_NO_MESH_LOCKSTEP", raising=False)


def _ragged_packs(lens, corrupt=(), crash_p=0.0, base_seed=7000):
    packs = []
    for i, n in enumerate(lens):
        h = fixtures.gen_history("cas", n_ops=n, processes=3,
                                 seed=base_seed + i, crash_p=crash_p)
        if i in corrupt:
            h = fixtures.corrupt(h, seed=i)
        packs.append(pack(h))
    return packs


def test_shard_groups_for_mesh_partitions_lanes():
    """Planner-level lane sharding: every lane still appears, extra
    occurrences are pad duplicates, singletons cannot split."""
    groups, pad = reach_batch.shard_groups_for_mesh([list(range(10))], 4)
    assert len(groups) >= 4
    seen = set().union(*[set(g) for g in groups])
    assert seen == set(range(10))
    assert sum(len(g) for g in groups) == 10 + pad
    groups2, pad2 = reach_batch.shard_groups_for_mesh([[0], [1]], 8)
    assert groups2 == [[0], [1]] and pad2 == 0
    # already enough groups: untouched
    orig = [[0, 1], [2, 3], [4]]
    groups3, pad3 = reach_batch.shard_groups_for_mesh(orig, 2)
    assert groups3 == orig and pad3 == 0


@needs_native
@needs_mesh
def test_mesh_matches_single_device_and_sequential(monkeypatch):
    """Ragged mix (H=10 keys, NOT divisible by 4 devices) spanning
    several buckets with two injected violations: mesh-lockstep
    verdicts, dead events, and witness ops bit-identical to the
    single-device lockstep scheduler AND the per-key sequential path;
    the obs ledger records route mesh-lockstep (not mesh-union) and
    every device dispatched at least one group."""
    lens = [220, 30, 90, 250, 45, 60, 150, 35, 40, 70]
    packs = _ragged_packs(lens, corrupt={0, 6})
    model = models.cas_register()
    refs = [reach.check_packed(model, p) for p in packs]
    _force_mesh(monkeypatch)
    devs = jax.devices()[:4]
    diag = {}
    with obs.capture() as cap:
        res = reach.check_many(model, packs, devices=devs, diag=diag)
    assert all(r["engine"] == "reach-lockstep-mesh" for r in res)
    routes = [r for r in cap.ledger if r.get("event") == "route"]
    assert any(r.get("cause") == "mesh-lockstep" for r in routes)
    assert not any(r.get("cause") == "mesh-union" for r in routes)
    mesh = diag.get("mesh")
    assert mesh and mesh["n_devices"] == 4
    assert all(c >= 1 for c in mesh["per_device_groups"])
    assert mesh["inflight_max"] >= 2        # genuinely multi-queued
    # single-device lockstep on the same batch
    res1 = reach.check_many(model, packs)
    assert all(r["engine"] == "reach-lockstep" for r in res1)
    n_bad = 0
    for i, (a, b, r) in enumerate(zip(res, res1, refs)):
        assert a["valid"] == b["valid"] == r["valid"], f"key {i}"
        if a["valid"] is False:
            n_bad += 1
            assert a["dead-event"] == b["dead-event"] == \
                r["dead-event"], f"key {i}"
            assert a["op"] == b["op"] == r["op"], f"key {i}"
            assert a.get("final-configs"), f"key {i} missing witness"
    assert n_bad >= 1                       # the corruptor worked


@needs_native
@needs_mesh
def test_mesh_check_batch_crashes_and_diag_threading(monkeypatch):
    """check_batch(devices=...) rides the mesh-lockstep lane with
    crashed ops in the mix, and its group=/diag= arguments are no
    longer dropped on the floor when a mesh is supplied."""
    lens = [200, 40, 90, 120, 45, 60, 35]
    packs = _ragged_packs(lens, corrupt={3}, crash_p=0.02,
                          base_seed=8100)
    model = models.cas_register()
    refs = [reach.check_packed(model, p) for p in packs]
    _force_mesh(monkeypatch)
    devs = jax.devices()[:4]
    diag = {}
    res = reach.check_batch(model, packs, devices=devs, diag=diag)
    assert all(r["engine"] == "reach-lockstep-mesh" for r in res)
    # the ISSUE-named small fix: diagnostics survive the mesh path
    assert diag.get("mesh", {}).get("n_devices") == 4
    assert diag.get("prep", {}).get("mode") == "stream"
    for i, (a, r) in enumerate(zip(res, refs)):
        assert a["valid"] == r["valid"], f"key {i}"
        if a["valid"] is False:
            assert a["dead-event"] == r["dead-event"], f"key {i}"


@needs_native
@needs_mesh
def test_forced_mesh_failure_falls_back_to_single_device_lockstep(
        monkeypatch):
    """A dispatch failure on the mesh records exactly ONE mesh-lockstep
    fallback and re-runs the batch on the SINGLE-DEVICE lockstep lane —
    the keyed kernel is NOT silently selected."""
    packs = _ragged_packs([180, 40, 90, 60, 45, 35], corrupt={2},
                          base_seed=9200)
    model = models.cas_register()
    refs = [reach.check_packed(model, p) for p in packs]
    _force_mesh(monkeypatch)
    orig = reach_batch.dispatch_prepared

    def boom(prep):
        if prep.device is not None:     # only mesh-placed dispatches
            raise RuntimeError("forced mesh dispatch failure")
        return orig(prep)

    monkeypatch.setattr(reach_batch, "dispatch_prepared", boom)
    with obs.capture() as cap:
        res = reach.check_many(model, packs,
                               devices=jax.devices()[:4])
    falls = [r for r in cap.fallbacks() if r["stage"] == "mesh-lockstep"]
    assert len(falls) == 1
    assert falls[0]["cause"] == "RuntimeError"
    # the single-device lockstep lane answered, NOT the keyed kernel
    assert all(r["engine"] == "reach-lockstep" for r in res)
    routes = [r for r in cap.ledger if r.get("event") == "route"]
    assert any(r.get("cause") == "lockstep" for r in routes)
    assert not any(r.get("cause") in ("mesh-union", "keyed")
                   for r in routes)
    for i, (a, r) in enumerate(zip(res, refs)):
        assert a["valid"] == r["valid"], f"key {i}"
        if a["valid"] is False:
            assert a["dead-event"] == r["dead-event"], f"key {i}"


@needs_native
@needs_mesh
def test_no_mesh_lockstep_env_opt_out(monkeypatch):
    """JEPSEN_TPU_NO_MESH_LOCKSTEP=1 skips the mesh-lockstep lane: the
    keyed mesh-union route answers as before the tentpole."""
    _force_mesh(monkeypatch)
    monkeypatch.setenv("JEPSEN_TPU_NO_MESH_LOCKSTEP", "1")
    packs = _ragged_packs([120, 60, 45, 80, 50], base_seed=6500)
    model = models.cas_register()
    with obs.capture() as cap:
        res = reach.check_many(model, packs, devices=jax.devices()[:4])
    routes = [r for r in cap.ledger if r.get("event") == "route"]
    assert any(r.get("cause") == "mesh-union" for r in routes)
    assert not any(r.get("cause") == "mesh-lockstep" for r in routes)
    assert all(r["valid"] is True for r in res)
    assert all(r["engine"] == "reach-batch" for r in res)


@needs_native
@needs_mesh
def test_walk_returns_batch_sharded_matches_single(monkeypatch):
    """Kernel-level differential: the sharded one-shot walk's dead
    indices equal the single-chip lockstep walk's, including a death,
    with H not divisible by the device count."""
    monkeypatch.setattr(reach_batch, "_adaptive_block", lambda H, W: 64)
    packs = _ragged_packs([90, 40, 60, 30, 50], corrupt={1},
                          base_seed=3300)
    model = models.cas_register()
    live = list(range(len(packs)))
    sa = reach._union_stage_a(model, packs, live, 100_000)
    assert sa is not None
    g = reach._union_pack_group(sa, live, 20)
    assert g is not None
    ret_flat, ops_flat, _key_W, _key_R, offsets, W = g
    P, M = sa.P(), 1 << W
    rets = [ret_flat[offsets[k]:offsets[k + 1]] for k in live]
    opss = [ops_flat[offsets[k]:offsets[k + 1]] for k in live]
    dead1 = reach_batch.walk_returns_batch(P, rets, opss, M,
                                           interpret=True)
    dead4 = reach_batch.walk_returns_batch_sharded(
        P, rets, opss, M, jax.devices()[:4], interpret=True)
    np.testing.assert_array_equal(dead1, dead4)
    assert (dead1 >= 0).any()       # the injected violation died
