"""Pipelined dispatch (ISSUE 20): the stage/collect split that keeps
K groups in flight per lane so device compute overlaps host pack and
fetch. Everything here runs against STUB device bodies with injected
latency — the contracts under test are scheduling ones: (a) K>1
actually overlaps (wall < the serial sum of stages), (b) verdict
order and content are bit-identical to the K=1 degenerate mode across
ragged group mixes, (c) a mid-window poison group collects into the
existing recovery ladder with exactly one staged fallback while its
window-mates complete clean, and (d) the per-lane attribution clock
reconciles: attributed device time sums to the lane's busy wall, not
to the (overlap-inflated) sum of per-group elapsed times."""
import threading
import time
import types

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jepsen_tpu import models, obs
from jepsen_tpu.serve import engine as serve_engine
from jepsen_tpu.serve import faults
from jepsen_tpu.serve import recovery
from jepsen_tpu.serve import request as rq
from jepsen_tpu.serve.coalesce import AdmissionQueue
from jepsen_tpu.checkers import dispatch_core, reach_batch


@pytest.fixture(autouse=True)
def _reset_faults():
    faults.reset()
    yield
    faults.reset()


# -- dispatch core: the stage/collect window ------------------------------

class _FakeFl:
    """A dispatched-but-unfetched group whose 'device walk' is a wall
    clock started at dispatch (the async-launch model: the launch
    returns immediately, the result is resident ``delay`` later, and
    a fetch before that blocks for the remainder)."""
    word_out = None
    final = None
    degraded = False

    def __init__(self, val, delay):
        self.geom = (1, 1, 1, 1, 1, 2, 1)       # B W M S H O1 R_pad
        self.R_lens = [1]
        self.dsegs = {}
        self.val = val
        self.t_done = time.monotonic() + delay

    def fetch(self):
        wait = self.t_done - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        return np.asarray([self.val], np.int64)


def _drive(k, n, host_s, dev_s, monkeypatch):
    """Stage n single-lane groups through a DispatchState window of
    depth k: ``host_s`` of synchronous host pack per group, ``dev_s``
    of simulated device walk after each launch."""
    monkeypatch.setattr(reach_batch, "collect_returns_batch",
                        lambda fl: fl.fetch())
    dead = np.full(n, -1, np.int64)
    st = dispatch_core.DispatchState(None, dead, k=k)
    t0 = time.monotonic()
    for gi in range(n):
        time.sleep(host_s)                       # the host pack stage
        prep = types.SimpleNamespace(device=None)
        di, _sp = st.place(gi, [gi], prep)
        st.admit([gi], _FakeFl(100 + gi, dev_s), di)
        st.drain(st.depth)
    st.drain(0)
    return time.monotonic() - t0, dead, st


def test_pipeline_k_resolution(monkeypatch):
    """K precedence: NO_PIPELINE collapses to 1, PIPE_K overrides,
    else the caller's default; DispatchState's window depth follows."""
    monkeypatch.setenv("JEPSEN_TPU_NO_PIPELINE", "1")
    assert not dispatch_core.pipeline_enabled()
    assert dispatch_core.pipeline_k(default=7) == 1
    monkeypatch.delenv("JEPSEN_TPU_NO_PIPELINE")
    assert dispatch_core.pipeline_enabled()
    monkeypatch.setenv("JEPSEN_TPU_PIPE_K", "3")
    assert dispatch_core.pipeline_k(default=7) == 3
    monkeypatch.delenv("JEPSEN_TPU_PIPE_K")
    assert dispatch_core.pipeline_k(default=7) == 7
    dead = np.full(4, -1, np.int64)
    assert dispatch_core.DispatchState(None, dead, k=1).depth == 0
    assert dispatch_core.DispatchState(None, dead, k=4).depth == 3


def test_stage_collect_overlap_and_bit_identity(monkeypatch):
    """K=4 over stub walks must beat the serial K=1 wall (the device
    clocks of queued groups run while later groups pack), and the
    collected verdict array must be IDENTICAL — same values, same
    order — to the degenerate mode's."""
    n, host_s, dev_s = 6, 0.02, 0.06
    c0 = obs.counters()
    w1, dead1, st1 = _drive(1, n, host_s, dev_s, monkeypatch)
    w4, dead4, st4 = _drive(4, n, host_s, dev_s, monkeypatch)
    assert dead1.tolist() == [100 + i for i in range(n)]
    assert dead4.tolist() == dead1.tolist()
    # serial pays host+device per group; pipelined pays host per group
    # plus ~one device drain — the overlap claim, with slack for CI
    assert w1 >= n * (host_s + dev_s) - 0.01
    assert w4 < 0.75 * w1, (w4, w1)
    assert st1.inflight_hwm == 1
    assert st4.inflight_hwm >= 2
    dc = {k: v - c0.get(k, 0) for k, v in obs.counters().items()}
    assert dc.get("pipeline.staged") == 2 * n


def test_collect_ready_stops_at_first_walking_group(monkeypatch):
    """Readiness-polled collect drains only resident predecessors and
    never polls past the first still-walking group (FIFO order is the
    verdict-order contract)."""
    monkeypatch.setattr(reach_batch, "collect_returns_batch",
                        lambda fl: np.asarray([fl.val], np.int64))

    class _Probe:
        def __init__(self):
            self.ok = False

        def is_ready(self):
            return self.ok

    dead = np.full(2, -1, np.int64)
    st = dispatch_core.DispatchState(None, dead, k=4)
    fls, probes = [], []
    for gi in range(2):
        fl = _FakeFl(100 + gi, 0.0)
        p = _Probe()
        fl.word_out = (p,)
        probes.append(p)
        prep = types.SimpleNamespace(device=None)
        di, _sp = st.place(gi, [gi], prep)
        st.admit([gi], fl, di)
        fls.append(fl)
    st.collect_ready(0)
    assert dead.tolist() == [-1, -1]            # nothing resident yet
    probes[1].ok = True                          # out-of-order ready:
    st.collect_ready(0)                          # FIFO must still wait
    assert dead.tolist() == [-1, -1]
    probes[0].ok = True
    st.collect_ready(0)
    assert dead.tolist() == [100, 101]
    assert st.inflight == []


# -- serve engine: the lane window over a stubbed staged facade -----------

class _Handle:
    """Staged-engine stub: launch starts the device clock, ``ready``
    polls it, ``collect`` blocks out the remainder then yields one
    result per packed entry (or dies, for the poison tests)."""

    def __init__(self, packed_list, delay, poison=False):
        self.packed_list = packed_list
        self.t_done = time.monotonic() + delay
        self.poison = poison

    def ready(self):
        return time.monotonic() >= self.t_done

    def collect(self):
        wait = self.t_done - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        if self.poison:
            raise RuntimeError("injected staged device death")
        return [{"valid": True, "engine": "stub",
                 "n": int(getattr(p, "n", -1))}
                for p in self.packed_list]


@pytest.fixture
def rig(monkeypatch):
    """Real Dispatcher over a stubbed facade: the staged route hands
    back latency-injected handles, the blocking/ladder routes answer
    instantly with the SAME per-packed verdicts (so K=1 vs K>1
    differentials compare engine-independent content)."""
    from jepsen_tpu.checkers import facade, wgl_ref

    state = {"delay": 0.0, "poison_at": None, "staged": 0, "many": 0}

    def _res(p):
        return {"valid": True, "engine": "stub",
                "n": int(getattr(p, "n", -1))}

    def fake_stage(model, packed_list, kw):
        state["staged"] += 1
        return _Handle(packed_list, state["delay"],
                       poison=(state["staged"] == state["poison_at"]))

    def fake_many(model, packed_list, kw):
        state["many"] += 1
        return [_res(p) for p in packed_list]

    monkeypatch.setattr(facade, "stage_check_many_packed", fake_stage)
    monkeypatch.setattr(facade, "auto_check_many_packed", fake_many)
    monkeypatch.setattr(facade, "auto_check_packed",
                        lambda model, p, kw: _res(p))
    monkeypatch.setattr(wgl_ref, "check_packed",
                        lambda model, p, **kw: _res(p))

    def build(**dkw):
        q = AdmissionQueue(max_depth=64, group=4)
        reg = rq.Registry()
        d = serve_engine.Dispatcher(
            q, reg,
            retry_policy=recovery.RetryPolicy(max_retries=1,
                                              base_s=0.001),
            **dkw)
        d.start()
        return d, q, reg
    return build, state


def _mk_req(n_ops=8, tenant="t", rid=None):
    return rq.CheckRequest(
        id=rid or rq.new_request_id(), tenant=tenant,
        model_name="cas-register", model=models.cas_register(),
        packed=types.SimpleNamespace(n=n_ops), history=[],
        n_ops=n_ops)


def _run(reg, q, reqs, timeout=30.0):
    for r in reqs:
        reg.add(r)
        q.submit(r)
    for r in reqs:
        assert r.done_event.wait(timeout), (r.id, r.status)


def _ragged_workload(n_groups=3, width=4):
    """n_groups × width requests, ragged op counts inside one
    coalescer length bucket, distinct tenants so no inflight cap
    interferes."""
    reqs = []
    for g in range(n_groups):
        for i in range(width):
            reqs.append(_mk_req(n_ops=8 + 4 * ((g + i) % 5),
                                tenant=f"g{g}t{i}"))
    return reqs


def test_lane_window_overlaps_and_matches_serial(rig, monkeypatch):
    """Three staged groups with 0.25 s device walks must finish in
    well under the 0.75 s serial sum, peak >=2 in flight, count
    overlap seconds — and every verdict must equal the K=1 run's for
    the same request (alignment through pads included)."""
    build, state = rig
    state["delay"] = 0.25
    monkeypatch.setenv("JEPSEN_TPU_PIPE_K", "4")
    c0 = obs.counters()
    d, q, reg = build()
    try:
        reqs = _ragged_workload()
        expect = {r.id: r.packed.n for r in reqs}
        t0 = time.monotonic()
        _run(reg, q, reqs)
        wall = time.monotonic() - t0
    finally:
        d.stop()
    assert state["staged"] >= 2, "window never staged"
    for r in reqs:
        assert r.status == rq.DONE
        assert r.result["valid"] is True
        # result i belongs to request i: the per-request op count
        # rode through stage -> collect -> publish unpermuted
        # (the registry drops the packed payload at finish, so
        # compare against the pre-run capture)
        assert r.result["n"] == expect[r.id], (r.id, r.result)
    n_staged_groups = state["staged"]
    assert wall < 0.25 * n_staged_groups * 0.9, \
        (wall, n_staged_groups)                 # overlap, not serial
    assert d._inflight_peak >= 2
    dc = {k: v - c0.get(k, 0) for k, v in obs.counters().items()}
    assert dc.get("pipeline.overlap_s", 0.0) > 0.0

    # the K=1 degenerate mode: same workload, blocking path only,
    # identical verdict content per request
    monkeypatch.setenv("JEPSEN_TPU_NO_PIPELINE", "1")
    staged_before = state["staged"]
    d1, q1, reg1 = build()
    try:
        reqs1 = _ragged_workload()
        _run(reg1, q1, reqs1)
    finally:
        d1.stop()
    assert state["staged"] == staged_before     # never staged at K=1
    by_tenant = {r.tenant: r.result for r in reqs}
    for r in reqs1:
        assert r.status == rq.DONE
        assert r.result == by_tenant[r.tenant], r.tenant


def test_mid_window_poison_group_ladder_and_lane_mates(rig,
                                                      monkeypatch):
    """The SECOND staged group's collect dies mid-window: it must
    drop into the unchanged recovery ladder (one staged serve-dispatch
    fallback, retry succeeds, every member completes) while the other
    window groups publish clean."""
    build, state = rig
    state["delay"] = 0.2
    state["poison_at"] = 2
    monkeypatch.setenv("JEPSEN_TPU_PIPE_K", "4")
    d, q, reg = build()
    try:
        reqs = _ragged_workload()
        expect = {r.id: r.packed.n for r in reqs}
        _run(reg, q, reqs)
    finally:
        d.stop()
    assert state["staged"] >= 2
    for r in reqs:
        assert r.status == rq.DONE
        assert r.result["valid"] is True
        assert r.result["n"] == expect[r.id]
    falls = [(r.id, t) for r in reqs for t in r.trace
             if t.get("event") == "fallback"
             and t.get("stage") == "serve-dispatch"]
    assert falls, "poison group recorded no staged fallback"
    assert all(t.get("staged") for _rid, t in falls)
    # exactly one poisoned GROUP: its members share the one fallback,
    # everyone else's trace is clean
    poisoned = {rid for rid, _t in falls}
    assert 2 <= len(poisoned) <= 4               # one group's members
    assert len({id(t) for _rid, t in falls}) <= 4


def test_attribution_reconciles_with_interleaved_groups(rig,
                                                        monkeypatch):
    """With K groups interleaved on one lane, the per-group elapsed
    walls OVERLAP — summing them would over-report device time by ~K.
    The attribution clock must instead sum to the lane's busy wall
    (<=2% over), with the deducted remainder counted as
    ``pipeline.overlap_s``."""
    build, state = rig
    state["delay"] = 0.25
    monkeypatch.setenv("JEPSEN_TPU_PIPE_K", "4")
    c0 = obs.counters()
    h0 = obs.histograms()
    d, q, reg = build()
    try:
        reqs = _ragged_workload()
        _run(reg, q, reqs)
    finally:
        d.stop()
    assert state["staged"] >= 2
    h1 = obs.histograms()
    att = (h1.get("serve.dispatch_wall_s", {}).get("sum", 0.0)
           - h0.get("serve.dispatch_wall_s", {}).get("sum", 0.0))
    lane_wall = (max(r.t_collect for r in reqs)
                 - min(r.t_dispatch for r in reqs))
    # per-group elapsed (the stitched trace's wall_s) still reports
    # full launch->collect spans, whose sum exceeds the lane wall
    # under overlap
    group_walls = [t["wall_s"] for r in reqs for t in r.trace
                   if t.get("event") == "dispatch"]
    assert att <= lane_wall * 1.02 + 0.02, (att, lane_wall)
    assert att >= state["delay"] * 0.5           # device time counted
    if sum(group_walls) > lane_wall * 1.1:
        dc = {k: v - c0.get(k, 0)
              for k, v in obs.counters().items()}
        assert dc.get("pipeline.overlap_s", 0.0) > 0.0
