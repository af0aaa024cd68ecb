"""Differential tests for the chunk-lockstep engine
(:mod:`jepsen_tpu.checkers.reach_chunklock`, interpret mode on CPU; on
TPU it is the first engine ``reach.check_packed`` tries at the
cas-100k/10M benchmark rungs). Verdicts AND dead indices must be
bit-identical to the sequential walk, across singleton-seed, union-seed
(``e_pad`` overflow), and rescue (loose ``suffix`` bound) regimes."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jepsen_tpu import fixtures, models, obs
from jepsen_tpu.checkers import events as ev
from jepsen_tpu.checkers import reach, reach_chunklock
from jepsen_tpu.history import pack


def _hists(kind, n, seeds, corrupt_seeds=()):
    out = []
    for s in seeds:
        hh = fixtures.gen_history(kind, n_ops=n, processes=4, seed=s)
        if s in corrupt_seeds:
            try:
                hh = fixtures.corrupt(hh, seed=s)
            except ValueError:
                pass
        out.append(hh)
    return out


def _assert_matches(model, packed, **kw):
    ref = reach.check_packed(model, packed)
    res = reach_chunklock.check_packed(model, packed, interpret=True,
                                       **kw)
    assert res["valid"] == ref["valid"], (kw, ref, res)
    if ref["valid"] is False:
        assert res["dead-event"] == ref["dead-event"], (ref, res)
        assert res["op"] == ref["op"]
        # the witness decoded from the dead chunk's entry set is the
        # one the reference decodes from the initial state
        assert res.get("final-configs") == ref["final-configs"], kw
        assert res.get("previous-ok") == ref.get("previous-ok"), kw
    return res


@pytest.mark.parametrize("kind,model_fn", [
    ("cas", models.cas_register),
    ("register", models.register),
    ("mutex", models.mutex),
])
def test_chunklock_matches_reference(kind, model_fn):
    model = model_fn()
    for i, hh in enumerate(_hists(kind, 120, range(5),
                                  corrupt_seeds=(1, 3))):
        _assert_matches(model, pack(hh), n_chunks=4, suffix=8,
                        e_pad=4)


def test_chunklock_union_seeds_and_rescue():
    """e_pad=1 forces EVERY multi-config boundary into one union seed;
    suffix=2 makes the bound loose — the rescue path must restore exact
    verdicts and dead indices."""
    model = models.cas_register()
    rescued = 0
    for i, hh in enumerate(_hists("cas", 150, range(6),
                                  corrupt_seeds=(2, 5))):
        res = _assert_matches(model, pack(hh), n_chunks=5, suffix=2,
                              e_pad=1)
        rescued += res.get("rescues", 0)
    assert rescued >= 1          # the loose bound did flag chunks


def test_chunklock_tight_bound_no_rescue():
    """With a full-chunk suffix the bound pass replays each chunk
    exactly, so boundaries are exact and no chunk is ever rescued."""
    model = models.cas_register()
    for hh in _hists("cas", 140, range(3)):
        p = pack(hh)
        res = _assert_matches(model, p, n_chunks=3, suffix=10_000,
                              e_pad=16)
        assert res.get("rescues", 0) == 0


def test_chunklock_dead_chunk_localization():
    """Violations in different chunks localize to the same return the
    sequential walk reports (first-empty semantics)."""
    model = models.cas_register()
    found = 0
    for s in range(8):
        hh = fixtures.gen_history("cas", n_ops=160, processes=5,
                                  seed=40 + s)
        try:
            hh = fixtures.corrupt(hh, seed=s)
        except ValueError:
            continue
        p = pack(hh)
        ref = reach.check_packed(model, p)
        if ref["valid"] is False:
            found += 1
            _assert_matches(model, p, n_chunks=6, suffix=6, e_pad=2)
    assert found >= 3


def test_chunklock_gates():
    model = models.cas_register()
    p = pack(fixtures.gen_history("cas", n_ops=60, processes=3,
                                  seed=7))
    with pytest.raises(reach_chunklock.ChunklockUnfit):
        # W beyond the exact-ladder cap is refused up front
        reach_chunklock.walk_chunklock(
            np.zeros((3, 2, 2), np.float32),
            np.zeros(40, np.int32),
            np.zeros((40, reach_chunklock._FAST_PASSES + 1), np.int32),
            4, interpret=True)
    # empty history short-circuits without device work
    from jepsen_tpu.history import pack as _pack
    res = reach_chunklock.check_packed(model, _pack([]))
    assert res["valid"] is True


def test_chunklock_fits_envelope():
    assert reach_chunklock.fits(8, 32, 5, 32, 8)
    assert not reach_chunklock.fits(64, 1 << 14, 8, 64, 32)


def test_chunklock_facade_algorithm():
    """The explicit ``chunklock`` facade algorithm routes engine
    options through and returns the branded verdict."""
    from jepsen_tpu.checkers import facade
    h = fixtures.gen_history("cas", n_ops=130, processes=4, seed=21)
    res = facade.linearizable(
        models.cas_register(), algorithm="chunklock", n_chunks=4,
        e_pad=2, suffix=8, interpret=True).check(None, h)
    assert res["valid"] is True
    assert res["engine"] == "reach-chunklock"


def _corrupt_read(seed, i):
    """The seeded 120-op CAS history with its op ``i`` (a successful
    read) observing a value no write produced: the walk dies at that
    read's return."""
    hh = fixtures.gen_history("cas", n_ops=120, processes=4, seed=seed)
    assert hh[i].type == "ok" and hh[i].f == "read"
    hh[i] = hh[i].with_(value=999_999)
    return pack(hh)


def _walked(cap):
    """The ``walked`` arg of each chunk-lockstep witness span."""
    return [s["args"]["walked"] for s in cap.spans
            if s["name"] == "reach.witness"
            and s["args"].get("engine") == "reach-chunklock"]


# seed 0's history has 93 returns: 4 chunks of 24. Op 37's return is
# return 11 (chunk 0), op 68's return 24 (chunk 1's first), op 157's
# return 58 (chunk 2, reached by the host refold under the loose bound)
@pytest.mark.parametrize("case,op,kw", [
    ("chunk0", 37, dict(n_chunks=4, suffix=8, e_pad=4)),
    ("chunk-first-return", 68, dict(n_chunks=4, suffix=8, e_pad=4)),
    ("rescue", 157, dict(n_chunks=4, suffix=2, e_pad=1)),
])
def test_chunklock_witness_seeded(case, op, kw):
    """The witness walk starts from the dead chunk's exact entry set on
    every route that localizes a death, and decodes the same evidence
    as the walk from the initial state."""
    model = models.cas_register()
    p = _corrupt_read(0, op)
    with obs.capture() as cap:
        res = _assert_matches(model, p, **kw)
    assert res["valid"] is False
    assert cap.counters.get("witness.seeded") == 1
    assert "witness.unseeded" not in cap.counters
    walked = _walked(cap)
    dead = res["max-linearized"]
    if case == "chunk0":
        assert walked == [dead] and dead > 0
    elif case == "chunk-first-return":
        assert walked == [0] and dead > 0
    else:
        assert res["rescues"] >= 1 and 0 < walked[0] < dead


def _set_before(P_np, rs, lo, S_pad, M, W):
    """The exact config set before return ``lo``: the unseeded walk of
    ``[0, lo)`` from the initial state (identity padding rows)."""
    xc, bm = reach._xor_bitmask(W, M)
    pre = ev.pad_returns(
        ev.ReturnStream(ret_slot=rs.ret_slot[:lo],
                        slot_ops=rs.slot_ops[:lo],
                        ret_event=rs.ret_event[:lo],
                        ret_entry=rs.ret_entry[:lo], W=W, n_returns=lo),
        max(reach._UNROLL, -(-lo // reach._UNROLL) * reach._UNROLL))
    R0 = np.zeros((S_pad, M), bool)
    R0[0, 0] = True
    _, R, alive, _ = reach._jitted_walk_returns()(
        P_np, xc, bm, pre.ret_slot, pre.slot_ops, R0)
    assert bool(alive)
    return np.asarray(R)


@pytest.mark.parametrize("lo", [0, 40, 87])
def test_final_configs_from_start_set(lo):
    """``start=(lo, R_lo)`` with the exact set before return ``lo``
    walks only ``[lo, dead)`` and returns the unseeded output; ``lo`` =
    the dead return walks nothing."""
    model = models.cas_register()
    p = _corrupt_read(0, 229)                    # dies at return 87
    memo, stream, _T, S_pad, M = reach._prep(
        model, p, max_states=100_000, max_slots=20, max_dense=1 << 22)
    W = max(stream.W, 1)
    rs = ev.returns_view(stream)
    P_np = reach._build_P(memo, S_pad)
    dead = reach.check_packed(model, p)["max-linearized"]
    assert dead == 87
    ref = reach._final_configs(memo, rs, P_np, S_pad, M, W, dead)
    R_lo = _set_before(P_np, rs, lo, S_pad, M, W)
    got = reach._final_configs(memo, rs, P_np, S_pad, M, W, dead,
                               start=(lo, R_lo))
    assert got == ref and len(ref) > 1


def test_check_packed_chunklock_branch_seeds_witness(monkeypatch):
    """``reach.check_packed``'s chunk-lockstep branch (the long
    histories' route on the chip) hands the walk's seed to the witness
    decode: same evidence as the unseeded decode, ``walked`` under one
    chunk."""
    from jepsen_tpu.checkers import reach_chunklock as rcl
    model = models.cas_register()
    p = _corrupt_read(0, 229)
    ref = reach.check_packed(model, p)
    walk = rcl.walk_seeded
    monkeypatch.setattr(reach, "_use_pallas", lambda: True)
    monkeypatch.setattr(rcl, "MIN_RETURNS", 32)
    monkeypatch.setattr(rcl, "walk_seeded", lambda *a, **k: walk(
        *a, n_chunks=4, suffix=8, e_pad=4, interpret=True, **k))
    with obs.capture() as cap:
        res = reach.check_packed(model, p)
    assert res["engine"] == "reach-chunklock"
    assert res["dead-event"] == ref["dead-event"]
    assert res["final-configs"] == ref["final-configs"]
    assert res["previous-ok"] == ref["previous-ok"]
    assert cap.counters.get("witness.seeded") == 1
    walked = _walked(cap)
    assert walked == [87 - 72]               # chunk 3 starts at 3 * 24
    assert all(not isinstance(v, np.ndarray) for v in res.values())
