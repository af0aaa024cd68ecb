"""Differential + fallback tests for the round-6 transfer diet
(ENGINE.md §"The transfer diet"): narrow/bit-packed wire formats,
on-device verdict reduction with lazy full-array fetch, and donated /
device-resident buffers. Verdicts, dead indices, AND witnesses must be
bit-identical to the round-5 (undieted) path across ragged buckets,
crashes, and injected violations; each optimization's forced failure
must record exactly ONE obs fallback and degrade — never a silent
wrong answer — and every env opt-out must restore the round-5 format.
"""
import importlib.util
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from jepsen_tpu import fixtures, models, obs
from jepsen_tpu.checkers import events as ev
from jepsen_tpu.checkers import (preproc_native, reach, reach_batch,
                                 reach_lane, transfer)
from jepsen_tpu.history import pack

needs_native = pytest.mark.skipif(
    not preproc_native.available(),
    reason="native preprocessing library unavailable")

_DIET_VARS = ("JEPSEN_TPU_NO_PACKED_XFER", "JEPSEN_TPU_NO_LAZY_FETCH",
              "JEPSEN_TPU_NO_DONATE")


@pytest.fixture(autouse=True)
def _diet_on(monkeypatch):
    """Every test starts from the shipping default (all three diet
    gates open) and a cold device-operand cache; opt-outs are set
    per-test."""
    for v in _DIET_VARS:
        monkeypatch.delenv(v, raising=False)
    transfer.clear_device_cache()
    yield
    transfer.clear_device_cache()


def _operands(model, history):
    packed = pack(history)
    memo, stream, _T, S_pad, M = reach._prep(
        model, packed, max_states=100_000, max_slots=20,
        max_dense=1 << 22)
    rs = ev.returns_view(stream)
    P = reach._build_P(memo, S_pad)
    R0 = np.zeros((S_pad, M), bool)
    R0[0, 0] = True
    return packed, rs, P, R0


def _batch_operands(model, hists):
    packed = [pack(h) for h in hists]
    preps = [reach._prep(model, p, max_states=100_000, max_slots=20,
                         max_dense=1 << 22) for p in packed]
    live = list(range(len(packed)))
    W = max(max(p[1].W, 1) for p in preps)
    M = 1 << W
    rss = [ev.returns_view(p[1]) for p in preps]
    P, ret_flat, ops_flat, _key_flat, offsets, _wide = \
        reach._keyed_operands(model, packed, rss, live, W, 100_000)
    ret_slots = [ret_flat[offsets[k]:offsets[k + 1]]
                 for k in range(len(packed))]
    slot_ops = [ops_flat[offsets[k]:offsets[k + 1]]
                for k in range(len(packed))]
    return packed, P, ret_slots, slot_ops, M


# -- wire-format primitives ----------------------------------------------

def test_idx_dtype_narrowing_and_overflow_guard():
    """Narrowest signed dtype per geometry, with the explicit int32
    overflow fallback counted — a too-wide geometry is visible, never
    silently mis-marshalled."""
    assert transfer.idx_dtype(36) is np.int8
    assert transfer.idx_dtype(127) is np.int8
    assert transfer.idx_dtype(128) is np.int16
    assert transfer.idx_dtype(32767) is np.int16
    with obs.capture() as cap:
        assert transfer.idx_dtype(40_000) is np.int32
    assert cap.counters.get("transfer.narrow_fallback") == 1


@pytest.mark.parametrize("n", [1, 7, 8, 9, 64, 1000])
def test_pack_bool_roundtrips_host_and_device(n):
    """pack_bool's bit order must be exactly what both unpack halves
    invert: numpy on the host fallback path, jnp.unpackbits inside the
    jitted programs."""
    rng = np.random.default_rng(n)
    a = rng.random(n) < 0.3
    packed = transfer.pack_bool(a)
    assert packed.dtype == np.uint8 and packed.size == -(-n // 8)
    np.testing.assert_array_equal(
        transfer.unpack_bool_host(packed, n).astype(bool), a)
    dev = jnp.unpackbits(jnp.asarray(packed), count=n)
    np.testing.assert_array_equal(np.asarray(dev).astype(bool), a)


def test_cached_put_identity_reuse_and_optout(monkeypatch):
    """Read-only operands are cached device-resident keyed by host
    identity + tag: same array hits (counting donate.reuse), an equal
    COPY misses (identity, not content), and the donate opt-out
    disables caching entirely."""
    host = np.arange(12, dtype=np.float32)
    built = []

    def build():
        built.append(1)
        return jax.device_put(host)

    with obs.capture() as cap:
        d1, hit1 = transfer.cached_put(host, "t", build)
        d2, hit2 = transfer.cached_put(host, "t", build)
    assert (hit1, hit2) == (False, True) and len(built) == 1
    assert d2 is d1
    assert cap.counters.get("donate.reuse") == 1
    _d3, hit3 = transfer.cached_put(host.copy(), "t", build)
    assert hit3 is False
    _d4, hit4 = transfer.cached_put(host, "other-tag", build)
    assert hit4 is False
    monkeypatch.setenv("JEPSEN_TPU_NO_DONATE", "1")
    transfer.clear_device_cache()
    _d5, hit5 = transfer.cached_put(host, "t", build)
    _d6, hit6 = transfer.cached_put(host, "t", build)
    assert (hit5, hit6) == (False, False)


def test_cached_put_byte_bound(monkeypatch):
    """The device-resident cache is byte-bounded as well as
    count-bounded: an over-budget operand is never cached, and FIFO
    eviction keeps the pinned host copies under the cap — a soak
    across many models cannot pin unbounded HBM."""
    monkeypatch.setattr(transfer, "_DEV_CACHE_MAX_BYTES", 4096)
    transfer.clear_device_cache()
    big = np.zeros(8192, np.uint8)
    _d, hit = transfer.cached_put(big, "t", lambda: "dev-big")
    _d2, hit2 = transfer.cached_put(big, "t", lambda: "dev-big2")
    assert (hit, hit2) == (False, False)    # over-budget: never cached
    smalls = [np.zeros(1500, np.uint8) for _ in range(4)]
    for s in smalls:
        transfer.cached_put(s, "t", lambda: "dev")
    total = sum(e[0].nbytes for e in transfer._DEV_CACHE.values())
    assert 0 < total <= 4096
    _d3, hit3 = transfer.cached_put(smalls[-1], "t", lambda: "dev")
    assert hit3 is True                     # newest survivor still hits
    transfer.clear_device_cache()


# -- single-history lane walk: packed vs round-5, every opt-out ----------

@pytest.mark.parametrize("optout", [None] + list(_DIET_VARS))
@pytest.mark.parametrize("corrupt", [False, True])
def test_lane_walk_identical_under_every_gate(monkeypatch, optout,
                                              corrupt):
    """Multi-segment lane walk (small _BLOCK forces the segmented
    pipeline, so bit-packed seeds, donation, and lazy fetch are all
    genuinely exercised): dead index and final config set bit-identical
    with the full diet, with each gate individually opted out, and on
    injected violations."""
    monkeypatch.setattr(reach_lane, "_BLOCK", 8)
    model = models.cas_register()
    h = fixtures.gen_history("cas", n_ops=120, processes=3, seed=17)
    if corrupt:
        h = fixtures.corrupt(h, seed=3)
    _packed, rs, P, R0, = _operands(model, h)
    # round-5 reference: every gate closed
    for v in _DIET_VARS:
        monkeypatch.setenv(v, "1")
    ref_dead, ref_R = reach_lane.walk_returns(
        P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    for v in _DIET_VARS:
        monkeypatch.delenv(v)
    if optout is not None:
        monkeypatch.setenv(optout, "1")
    with obs.capture() as cap:
        dead, R_out = reach_lane.walk_returns(
            P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    assert dead == ref_dead
    if ref_R is None:
        assert R_out is None
    else:
        np.testing.assert_array_equal(R_out, ref_R)
    assert not [f for f in cap.fallbacks()
                if f["stage"] in ("packed-xfer", "lazy-fetch", "donate")]
    c = cap.counters
    if optout != "JEPSEN_TPU_NO_LAZY_FETCH":
        assert c.get("fetch.lazy", 0) > 0
        assert not c.get("fetch.eager")
    else:
        assert c.get("fetch.eager", 0) > 0
        assert not c.get("fetch.lazy")
    if optout != "JEPSEN_TPU_NO_DONATE":
        assert c.get("donate.reuse", 0) > 0      # multi-segment walk


def test_lane_packed_wire_is_smaller(monkeypatch):
    """The packed operand set must actually be smaller: pack_operands
    under the diet vs with the packed-transfer gate closed."""
    model = models.cas_register()
    h = fixtures.gen_history("cas", n_ops=200, processes=3, seed=5)
    _packed, rs, P, R0 = _operands(model, h)
    _g, _r, _s, host_args = reach_lane.pack_operands(
        P, rs.ret_slot, rs.slot_ops, R0)
    monkeypatch.setenv("JEPSEN_TPU_NO_PACKED_XFER", "1")
    _g2, _r2, _s2, host_args5 = reach_lane.pack_operands(
        P, rs.ret_slot, rs.slot_ops, R0)
    diet = sum(a.nbytes for a in host_args)
    round5 = sum(a.nbytes for a in host_args5)
    assert diet < round5
    # the seed tensor alone shrinks 32x (f32 -> 1 bit per config)
    assert host_args[3].nbytes * 8 <= host_args5[3].nbytes // 4 + 8


# -- lockstep batch walk: ragged buckets, crashes, violations ------------

def _ragged_hists(lens, corrupt=(), crash_p=0.0, base_seed=6100):
    hists = []
    for i, n in enumerate(lens):
        h = fixtures.gen_history("cas", n_ops=n, processes=3,
                                 seed=base_seed + i, crash_p=crash_p)
        if i in corrupt:
            h = fixtures.corrupt(h, seed=i)
        hists.append(h)
    return hists


@pytest.mark.parametrize("optout", [None] + list(_DIET_VARS))
def test_batch_walk_identical_under_every_gate(monkeypatch, optout):
    """Ragged lockstep batch with crashes and injected violations:
    dead indices bit-identical to the round-5 wire format under the
    full diet and under each individual opt-out."""
    model = models.cas_register()
    hists = _ragged_hists([150, 40, 170, 60, 155], corrupt={0, 3},
                          crash_p=0.02)
    _packed, P, ret_slots, slot_ops, M = _batch_operands(model, hists)
    for v in _DIET_VARS:
        monkeypatch.setenv(v, "1")
    ref = reach_batch.walk_returns_batch(P, ret_slots, slot_ops, M,
                                         interpret=True)
    for v in _DIET_VARS:
        monkeypatch.delenv(v)
    if optout is not None:
        monkeypatch.setenv(optout, "1")
    with obs.capture() as cap:
        dead = reach_batch.walk_returns_batch(P, ret_slots, slot_ops, M,
                                              interpret=True)
    np.testing.assert_array_equal(dead, ref)
    assert (dead >= 0).sum() >= 2                # violations surfaced
    assert not [f for f in cap.fallbacks()
                if f["stage"] in ("packed-xfer", "lazy-fetch", "donate")]
    c = cap.counters
    if optout != "JEPSEN_TPU_NO_LAZY_FETCH":
        assert c.get("fetch.lazy", 0) > 0
    else:
        assert c.get("fetch.eager", 0) > 0 and not c.get("fetch.lazy")


def test_batch_transition_tensor_uploaded_once(monkeypatch):
    """The union transition tensor P is device-cached across the group
    sequence: a second dispatch of the same P reuses group 1's buffer
    (donate.reuse counts the hit) instead of re-uploading."""
    model = models.cas_register()
    hists = _ragged_hists([90, 80], base_seed=6400)
    _packed, P, ret_slots, slot_ops, M = _batch_operands(model, hists)
    with obs.capture() as cap:
        reach_batch.walk_returns_batch(P, ret_slots[:1], slot_ops[:1],
                                       M, interpret=True)
        reach_batch.walk_returns_batch(P, ret_slots[1:], slot_ops[1:],
                                       M, interpret=True)
    assert cap.counters.get("donate.reuse", 0) >= 1


# -- forced failures: exactly one fallback, verdicts preserved -----------

def test_forced_donate_failure_exactly_once_batch(monkeypatch):
    """A donated dispatch failing must record exactly ONE `donate`
    fallback and finish the walk on the undonated jit with identical
    verdicts."""
    model = models.cas_register()
    hists = _ragged_hists([150, 145, 160], base_seed=6200)
    _packed, P, ret_slots, slot_ops, M = _batch_operands(model, hists)
    ref = reach_batch.walk_returns_batch(P, ret_slots, slot_ops, M,
                                         interpret=True)
    orig = reach_batch._batch_call

    def boom(*a):
        if len(a) > 10 and a[10]:            # the donate variant
            raise RuntimeError("forced donate failure")
        return orig(*a)

    monkeypatch.setattr(reach_batch, "_batch_call", boom)
    with obs.capture() as cap:
        dead = reach_batch.walk_returns_batch(P, ret_slots, slot_ops,
                                              M, interpret=True)
    np.testing.assert_array_equal(dead, ref)
    falls = [f for f in cap.fallbacks() if f["stage"] == "donate"]
    assert len(falls) == 1, falls
    assert falls[0]["cause"] == "RuntimeError"


def test_forced_lazy_fetch_failure_degrades_to_eager(monkeypatch):
    """A summary-reduction failure must record exactly ONE `lazy-fetch`
    fallback and degrade that collect to eager full-array fetches —
    verdicts (including the injected violation) identical."""
    model = models.cas_register()
    hists = _ragged_hists([90, 85, 95], corrupt={1}, base_seed=6300)
    _packed, P, ret_slots, slot_ops, M = _batch_operands(model, hists)
    ref = reach_batch.walk_returns_batch(P, ret_slots, slot_ops, M,
                                         interpret=True)

    def boom(H, S):
        raise RuntimeError("forced summary failure")

    monkeypatch.setattr(reach_batch, "_alive_lanes_call", boom)
    with obs.capture() as cap:
        dead = reach_batch.walk_returns_batch(P, ret_slots, slot_ops,
                                              M, interpret=True)
    np.testing.assert_array_equal(dead, ref)
    falls = [f for f in cap.fallbacks() if f["stage"] == "lazy-fetch"]
    assert len(falls) == 1, falls
    assert cap.counters.get("fetch.eager", 0) > 0


def test_forced_packed_dispatch_failure_retries_dense(monkeypatch):
    """A bit-packed seed dispatch failing must record exactly ONE
    `packed-xfer` fallback, re-materialize the dense seed host-side,
    and retry the round-5 wire format — identical verdict."""
    monkeypatch.setattr(reach_lane, "_BLOCK", 8)
    model = models.cas_register()
    h = fixtures.gen_history("cas", n_ops=120, processes=3, seed=23)
    _packed, rs, P, R0 = _operands(model, h)
    monkeypatch.setenv("JEPSEN_TPU_NO_PACKED_XFER", "1")
    ref_dead, ref_R = reach_lane.walk_returns(
        P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    monkeypatch.delenv("JEPSEN_TPU_NO_PACKED_XFER")
    orig = reach_lane._lane_call

    def fake(*a):
        run = orig(*a)

        def wrapped(*args):
            if args[3].dtype == jnp.uint8:
                raise RuntimeError("forced packed failure")
            return run(*args)

        return wrapped

    monkeypatch.setattr(reach_lane, "_lane_call", fake)
    with obs.capture() as cap:
        dead, R_out = reach_lane.walk_returns(
            P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    assert dead == ref_dead
    if ref_R is not None:
        np.testing.assert_array_equal(R_out, ref_R)
    falls = [f for f in cap.fallbacks() if f["stage"] == "packed-xfer"]
    assert len(falls) == 1, falls


def test_forced_packed_failure_mid_walk_under_donation(monkeypatch):
    """A packed-wire failure at segment i>0 first surfaces through the
    donated dispatch: the walk must record ONE `donate` fallback, then
    — when the undonated replay hits the same packed error — ONE
    `packed-xfer` fallback, degrade to the dense round-5 format, and
    still return the identical verdict (the bug: the packed recovery
    was unreachable behind the donate branch)."""
    monkeypatch.setattr(reach_lane, "_BLOCK", 8)
    model = models.cas_register()
    h = fixtures.gen_history("cas", n_ops=120, processes=3, seed=23)
    _packed, rs, P, R0 = _operands(model, h)
    monkeypatch.setenv("JEPSEN_TPU_NO_PACKED_XFER", "1")
    ref_dead, ref_R = reach_lane.walk_returns(
        P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    monkeypatch.delenv("JEPSEN_TPU_NO_PACKED_XFER")
    orig = reach_lane._lane_call
    calls = {"n": 0}

    def fake(*a):
        run = orig(*a)

        def wrapped(*args):
            # let segment 0 through, then fail every sextet-packed
            # dispatch — donated or not — until the dense rebuild
            if args[1].dtype == jnp.uint8:
                calls["n"] += 1
                if calls["n"] > 1:
                    raise RuntimeError("forced packed failure")
            return run(*args)

        return wrapped

    monkeypatch.setattr(reach_lane, "_lane_call", fake)
    with obs.capture() as cap:
        dead, R_out = reach_lane.walk_returns(
            P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    assert dead == ref_dead
    if ref_R is not None:
        np.testing.assert_array_equal(R_out, ref_R)
    stages = [f["stage"] for f in cap.fallbacks()]
    assert stages.count("packed-xfer") == 1, stages
    assert stages.count("donate") == 1, stages


def test_forced_pallas_packed_failure_retries_dense(monkeypatch):
    """The Pallas kernel honours the same packed-wire contract as the
    other engines: a failing packed dispatch records exactly ONE
    `packed-xfer` fallback and retries the dense round-5 format with a
    bit-identical dead index and final set."""
    from jepsen_tpu.checkers import reach_pallas

    model = models.cas_register()
    h = fixtures.gen_history("cas", n_ops=60, processes=3, seed=9)
    _packed, rs, P, R0 = _operands(model, h)
    monkeypatch.setenv("JEPSEN_TPU_NO_PACKED_XFER", "1")
    ref_dead, ref_R = reach_pallas.walk_returns(
        P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    monkeypatch.delenv("JEPSEN_TPU_NO_PACKED_XFER")
    orig = reach_pallas._walk_call

    def fake(*a):
        run = orig(*a)

        def wrapped(rlim, ret_slot, slot_ops, R0d, Pd):
            if getattr(R0d, "dtype", None) == np.uint8:
                raise RuntimeError("forced packed failure")
            return run(rlim, ret_slot, slot_ops, R0d, Pd)

        return wrapped

    monkeypatch.setattr(reach_pallas, "_walk_call", fake)
    with obs.capture() as cap:
        dead, R_out = reach_pallas.walk_returns(
            P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    assert dead == ref_dead
    if ref_R is None:
        assert R_out is None
    else:
        np.testing.assert_array_equal(R_out, ref_R)
    falls = [f for f in cap.fallbacks() if f["stage"] == "packed-xfer"]
    assert len(falls) == 1, falls


@pytest.mark.parametrize("corrupt", [False, True])
def test_chunklock_identical_packed_vs_dense_seeds(monkeypatch,
                                                   corrupt):
    """The chunk-lockstep walk's phase-A seeds cross bit-packed:
    verdict and dead event bit-identical to the dense round-5 seeds."""
    from jepsen_tpu.checkers import reach_chunklock

    model = models.cas_register()
    h = fixtures.gen_history("cas", n_ops=400, processes=3, seed=4)
    if corrupt:
        h = fixtures.corrupt(h, seed=4)
    p = pack(h)
    res = reach_chunklock.check_packed(model, p, interpret=True)
    monkeypatch.setenv("JEPSEN_TPU_NO_PACKED_XFER", "1")
    ref = reach_chunklock.check_packed(model, p, interpret=True)
    assert res["valid"] == ref["valid"]
    assert res.get("dead-event") == ref.get("dead-event")


@pytest.mark.parametrize("corrupt", [False, True])
def test_pallas_kernel_identical_packed_vs_round5(monkeypatch,
                                                  corrupt):
    """The first-generation Pallas kernel on the narrow/bit-packed
    wire format: dead index and final config set bit-identical to the
    blanket int32/f32 operands."""
    from jepsen_tpu.checkers import reach_pallas

    model = models.cas_register()
    h = fixtures.gen_history("cas", n_ops=60, processes=3, seed=8)
    if corrupt:
        h = fixtures.corrupt(h, seed=8)
    _packed, rs, P, R0 = _operands(model, h)
    dead, R_out = reach_pallas.walk_returns(
        P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    monkeypatch.setenv("JEPSEN_TPU_NO_PACKED_XFER", "1")
    ref_dead, ref_R = reach_pallas.walk_returns(
        P, rs.ret_slot, rs.slot_ops, R0, interpret=True)
    assert dead == ref_dead
    if ref_R is None:
        assert R_out is None
    else:
        np.testing.assert_array_equal(R_out, ref_R)


# -- scheduler-level witness identity through the lazy-fetch path --------

def _force_lockstep(monkeypatch):
    monkeypatch.setattr(reach, "_use_pallas", lambda: True)
    monkeypatch.setattr(reach, "_PALLAS_MIN_RETURNS", 0)
    monkeypatch.setattr(reach_batch, "_INTERPRET_DEFAULT", True)


@needs_native
def test_lockstep_witness_identical_through_lazy_fetch(monkeypatch):
    """check_many through the lockstep scheduler: the lazy-fetch path
    must reconstruct the IDENTICAL knossos-style witness (final-configs,
    previous-ok, failing op) as the eager round-5 path across a ragged
    mix with injected violations and crashes."""
    model = models.cas_register()
    lens = [180, 40, 90, 200, 45, 60]
    packs = [pack(h) for h in _ragged_hists(lens, corrupt={0, 3},
                                            crash_p=0.01,
                                            base_seed=6500)]
    _force_lockstep(monkeypatch)
    with obs.capture() as cap:
        res = reach.check_many(model, packs)
    assert all(r["engine"] == "reach-lockstep" for r in res)
    assert cap.counters.get("fetch.lazy", 0) > 0
    assert not [f for f in cap.fallbacks()
                if f["stage"] in ("packed-xfer", "lazy-fetch", "donate")]
    for v in _DIET_VARS:
        monkeypatch.setenv(v, "1")
    res5 = reach.check_many(model, packs)
    n_bad = 0
    for i, (a, b) in enumerate(zip(res, res5)):
        assert a["valid"] == b["valid"], f"key {i}"
        if a["valid"] is False:
            n_bad += 1
            assert a["dead-event"] == b["dead-event"], f"key {i}"
            assert a["op"] == b["op"], f"key {i}"
            assert a.get("final-configs") == b.get("final-configs"), \
                f"key {i} witness drifted"
            assert a.get("final-configs"), f"key {i} missing witness"
            assert a.get("previous-ok") == b.get("previous-ok")
    assert n_bad >= 2


@needs_native
def test_lockstep_diag_reports_transfer_breakdown(monkeypatch):
    """diag["transfer"] must carry the per-batch wire accounting the
    bench batch/independent sub-objects surface: packed bytes strictly
    below the blanket format, and the active fetch protocol."""
    model = models.cas_register()
    packs = [pack(h) for h in _ragged_hists([120, 110, 130],
                                            base_seed=6600)]
    _force_lockstep(monkeypatch)
    diag = {}
    res = reach.check_many(model, packs, diag=diag)
    assert all(r["valid"] is True for r in res)
    xfer = diag.get("transfer")
    assert xfer is not None
    assert 0 < xfer["packed_bytes"] < xfer["unpacked_bytes"]
    assert xfer["fetch_mode"] == "lazy"


@needs_native
def test_lockstep_diag_fetch_mode_reflects_degrade(monkeypatch):
    """When a lazy-fetch fallback forces a collect to eager mid-run,
    diag["transfer"]["fetch_mode"] must say `degraded-eager` — the
    protocol the verdicts ACTUALLY crossed on, not the env gate."""
    model = models.cas_register()
    packs = [pack(h) for h in _ragged_hists([120, 110, 130],
                                            base_seed=6600)]
    _force_lockstep(monkeypatch)
    ref = reach.check_many(model, packs)

    def boom(H, S):
        raise RuntimeError("forced summary failure")

    monkeypatch.setattr(reach_batch, "_alive_lanes_call", boom)
    diag = {}
    with obs.capture() as cap:
        res = reach.check_many(model, packs, diag=diag)
    assert [r["valid"] for r in res] == [r["valid"] for r in ref]
    assert diag["transfer"]["fetch_mode"] == "degraded-eager"
    assert [f for f in cap.fallbacks() if f["stage"] == "lazy-fetch"]


# -- the CI guard's budget logic -----------------------------------------

def _load_guard():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "transfer_guard", os.path.join(root, "tools",
                                       "transfer_guard.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_transfer_guard_check_logic():
    guard = _load_guard()
    budget = {"max_packed_bytes": 1000, "min_ratio": 3.0}
    ok = {"transfer": {"packed_bytes": 900, "unpacked_bytes": 3600,
                       "ratio": 4.0, "fetch_mode": "lazy",
                       "gates": {"packed": True, "lazy_fetch": True,
                                 "donate": True}}}
    assert guard.check(ok, budget)["ok"] is True
    fat = {"transfer": dict(ok["transfer"], packed_bytes=1200)}
    assert guard.check(fat, budget)["ok"] is False
    thin = {"transfer": dict(ok["transfer"], ratio=2.0)}
    assert guard.check(thin, budget)["ok"] is False
    # a CI env var opting the diet out must not let a regression hide
    gated = {"transfer": dict(ok["transfer"],
                              gates={"packed": False,
                                     "lazy_fetch": True,
                                     "donate": True})}
    assert guard.check(gated, budget)["ok"] is False
    # a broken/missing probe must not pass
    assert guard.check({}, budget)["ok"] is False
    assert guard.check({"transfer": {"error": "X"}}, budget)["ok"] \
        is False


def test_transfer_probe_reports_diet(monkeypatch):
    """bench.transfer_probe (the guard's measurement, host-only): the
    production operand packing under the diet must report well below
    the blanket int32/f32 format on a real history. The P transition
    tensor crosses as f32 either way and amortizes with history
    length, so the small history here clears a lower floor than the
    budget's 4.0x at the 20k-op quick config (the ratio grows with
    history length: ~4.4x at 20k)."""
    import bench

    model = models.cas_register()
    packed = pack(fixtures.gen_history("cas", n_ops=2000, processes=5,
                                       seed=42))
    out = bench.transfer_probe(model, packed)
    assert out["packed_bytes"] > 0
    assert out["ratio"] >= 2.5
    assert out["fetch_mode"] == "lazy"
    assert out["gates"] == {"packed": True, "lazy_fetch": True,
                            "donate": True}
