"""Span records of :mod:`jepsen_tpu.obs`: ids, parents and roots across
threads, the bridge into the ``jax.profiler`` trace, and where the
checker's memo tier, witness decode and serve roots record their spans."""
import contextvars
import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from jepsen_tpu import fixtures, models, obs
from jepsen_tpu.checkers import preproc_native, reach, reach_batch
from jepsen_tpu.history import pack
from jepsen_tpu.obs import core


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def _one(spans, name):
    (s,) = [s for s in spans if s["name"] == name]
    return s


def _ancestors(span, spans):
    """Names of ``span``'s ancestors, innermost first."""
    by_id = {s["args"]["id"]: s for s in spans}
    out, pid = [], span["args"]["parent"]
    while pid:
        p = by_id[pid]
        out.append(p["name"])
        pid = p["args"]["parent"]
    return out


# -- parents, roots, reqs ------------------------------------------------

def test_parent_root_and_reqs_nest_across_copied_context_thread():
    def work(name="worker"):
        with obs.span(name):
            with obs.span("leaf"):
                pass

    with obs.capture() as cap:
        with obs.span("req.root", reqs=["r1", "r2"]):
            with obs.span("child"):
                ctx = contextvars.copy_context()
                t = threading.Thread(target=lambda: ctx.run(work))
                t.start()
                t.join(10)
            # a thread not run under a copy starts a tree of its own
            loose = threading.Thread(target=work, args=("loose.worker",))
            loose.start()
            loose.join(10)
        with obs.span("next.root"):
            pass
    spans = cap.spans
    root = _one(spans, "req.root")
    child = _one(spans, "child")
    assert root["args"]["parent"] == 0
    assert root["args"]["root"] == root["args"]["id"]
    assert root["args"]["reqs"] == ["r1", "r2"]
    assert child["args"]["parent"] == root["args"]["id"]
    nested = _one(spans, "worker")
    assert nested["args"]["parent"] == child["args"]["id"]
    assert nested["args"]["root"] == root["args"]["id"]
    leaf = _one(spans, "leaf")
    assert leaf["args"]["parent"] == nested["args"]["id"]
    assert leaf["args"]["root"] == root["args"]["id"]
    (alone,) = [s for s in core.GLOBAL.span_events()
                if s["name"] == "loose.worker"]
    assert alone["args"]["parent"] == 0
    assert alone["args"]["root"] == alone["args"]["id"]
    nxt = _one(spans, "next.root")
    assert nxt["args"]["parent"] == 0
    assert len({s["args"]["id"] for s in spans}) == len(spans)
    # the open-span context is restored once every span has closed
    assert core._OPEN.get() == (0, 0)


def test_span_ids_survive_both_export_formats(tmp_path):
    with obs.capture() as cap:
        with obs.span("outer", reqs=["q"]):
            with obs.span("inner"):
                pass
    for name, export in (("obs.jsonl", obs.export_jsonl),
                         ("trace.json", obs.export_trace)):
        path = str(tmp_path / name)
        export(path, cap)
        spans = obs.load_any(path)["spans"]
        outer, inner = _one(spans, "outer"), _one(spans, "inner")
        assert inner["args"]["parent"] == outer["args"]["id"]
        assert inner["args"]["root"] == outer["args"]["id"]
        assert outer["args"]["reqs"] == ["q"]


def test_span_error_keeps_ids():
    with obs.capture() as cap:
        with pytest.raises(KeyError):
            with obs.span("boom"):
                raise KeyError("x")
    s = _one(cap.spans, "boom")
    assert s["args"]["error"] == "KeyError" and s["args"]["parent"] == 0


# -- the profiler bridge -------------------------------------------------

class _FakeAnnotation:
    enabled = False
    entered = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.entered.append(self.name)

    def __exit__(self, *exc):
        self.entered.append("/" + self.name)


def test_no_annotation_without_a_profiler_session(monkeypatch):
    monkeypatch.setattr(core, "_ANNOTATION", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "entered", [])
    with obs.span("quiet"):
        pass
    assert _FakeAnnotation.entered == []
    monkeypatch.setattr(_FakeAnnotation, "enabled", True)
    with obs.span("loud"):
        with obs.span("inner"):
            pass
    assert _FakeAnnotation.entered == ["loud", "inner", "/inner", "/loud"]


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with obs.span("obs.bridge.probe"):
            jnp.ones(8).block_until_ready()
    paths = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert paths
    pd = ProfileData.from_file(paths[0])
    host = [e.name for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    assert "obs.bridge.probe" in host


# -- where the checker records them --------------------------------------

def _persist_on(monkeypatch, tmp_path):
    monkeypatch.setenv("JEPSEN_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("JEPSEN_TPU_NO_PERSIST", raising=False)
    with reach._MEMO_CACHE_LOCK:
        reach._MEMO_CACHE.clear()
        reach._SUPERSET_SEEDS.clear()
        reach._SUPERSET_SEEDS_FAILED.clear()


def _evict_under_put(spans):
    ev = _by_name(spans)["memo.disk.evict"]
    for e in ev:
        assert _ancestors(e, spans)[0] == "memo.disk.put"
        assert e["args"]["listed"] >= 1 and e["args"]["evicted"] == 0
    return ev


def test_invalid_check_packed_spans_memo_and_witness(tmp_path,
                                                     monkeypatch):
    _persist_on(monkeypatch, tmp_path)
    p = pack(fixtures.corrupt(fixtures.gen_history(
        "cas", n_ops=200, processes=3, seed=11)))
    with obs.capture() as cap:
        res = reach.check_packed(models.cas_register(), p)
    assert res["valid"] is False and "final-configs" in res
    spans = cap.spans
    names = _by_name(spans)
    (wit,) = names["reach.witness"]
    walk = _one(spans, "reach.walk")
    # the walk's span closes on the verdict, before the decode starts
    assert walk["ts"] + walk["dur"] <= wit["ts"]
    for n in ("memo.disk.get", "memo.build", "memo.disk.put"):
        (s,) = names[n]
        assert "reach.prep" in _ancestors(s, spans)
    _evict_under_put(spans)


@pytest.mark.skipif(not preproc_native.available(),
                    reason="native preprocessing library unavailable")
def test_check_many_witness_spans_nest_the_memo_tier(tmp_path,
                                                     monkeypatch):
    """The lockstep lane (opened on the CPU with the batch kernel in
    interpret mode) decodes the invalid key in its own geometry."""
    _persist_on(monkeypatch, tmp_path)
    monkeypatch.setattr(reach, "_use_pallas", lambda: True)
    monkeypatch.setattr(reach, "_PALLAS_MIN_RETURNS", 0)
    monkeypatch.setattr(reach_batch, "_INTERPRET_DEFAULT", True)
    hs = [fixtures.gen_history("cas", n_ops=60, processes=3, seed=s)
          for s in range(6)]
    hs[2] = fixtures.corrupt(hs[2])
    with obs.capture() as cap:
        res = reach.check_many(models.cas_register(),
                               [pack(x) for x in hs])
    assert [r["valid"] for r in res] == [True, True, False, True, True,
                                         True]
    assert {r["engine"] for r in res} == {"reach-lockstep"}
    spans = cap.spans
    names = _by_name(spans)
    (wit,) = names["reach.witness"]
    for n in ("memo.disk.get", "memo.build", "memo.disk.put"):
        inside = [s for s in names[n]
                  if "reach.witness" in _ancestors(s, spans)]
        assert len(inside) == 1, n
        assert inside[0]["args"]["root"] == wit["args"]["root"]
    _evict_under_put(spans)
    assert "union.stage_a" in names


def test_disk_evict_span_counts_what_it_listed_and_evicted(tmp_path,
                                                           monkeypatch):
    _persist_on(monkeypatch, tmp_path)
    monkeypatch.setattr(reach, "_DISK_MEMO_MAX_ENTRIES", 3)
    d = os.path.join(str(tmp_path), "memo")
    os.makedirs(d)
    for k in range(4):
        path = os.path.join(d, f"old-{k}.memo.pkl")
        open(path, "wb").close()
        os.utime(path, (0, 0))
    p = pack(fixtures.gen_history("cas", n_ops=40, processes=3, seed=5))
    with obs.capture() as cap:
        reach._cached_memo(models.cas_register(), p, 100_000)
    (ev,) = _by_name(cap.spans)["memo.disk.evict"]
    assert ev["args"]["listed"] == 5 and ev["args"]["evicted"] == 2
    assert len(os.listdir(d)) == 3


def _synthetic_put(k):
    """Store a one-state memo under a signature of its own; return the
    entry's path, backdated to mtime ``k + 1`` so store order is the
    mtime order whatever the file system's clock resolution."""
    from jepsen_tpu.models.memo import Memo
    model = models.cas_register()
    sig = (model, 100_000, (("synthetic", k),))
    reach._disk_memo_put(sig, Memo(table=np.zeros((1, 1), np.int32),
                                   states=(model,), distinct_ops=()))
    path = reach._disk_memo_path(sig)[0]
    os.utime(path, (k + 1, k + 1))
    return path


def _disk_tier_small(monkeypatch, tmp_path):
    """Cap 8, so slack 1; a fresh per-process tally."""
    _persist_on(monkeypatch, tmp_path)
    monkeypatch.setattr(reach, "_DISK_MEMO_MAX_ENTRIES", 8)
    monkeypatch.setattr(reach, "_DISK_MEMO_TALLY", {})
    assert reach._disk_memo_evict_slack() == 1
    return os.path.join(str(tmp_path), "memo")


def test_disk_evict_scan_is_amortised_over_slack_stores(tmp_path,
                                                        monkeypatch):
    d = _disk_tier_small(monkeypatch, tmp_path)
    paths = []
    with obs.capture() as cap:
        for k in range(20):
            paths.append(_synthetic_put(k))
            assert len(os.listdir(d)) <= 8 + 1
            newest = paths[-8:]
            assert all(os.path.exists(p) for p in newest), k
    # the first store scans; the tally then reaches cap + slack = 9 at
    # the 9th store, and from the 10th on every 2nd store passes it
    scans = [0, 9, 11, 13, 15, 17, 19]
    assert cap.counters["memo_cache.disk.store"] == 20
    assert cap.counters["memo_cache.disk.scan"] == len(scans)
    ev = _evict_spans(cap.spans)
    assert [(e["args"]["listed"], e["args"]["evicted"]) for e in ev] == \
        [(1, 0)] + [(10, 2)] * 6
    assert cap.counters["memo_cache.disk.evict"] == 12
    assert sorted(os.listdir(d)) == sorted(os.path.basename(p)
                                           for p in paths[-8:])


def test_disk_evict_scan_catches_another_process_entries(tmp_path,
                                                         monkeypatch):
    """Entries written straight into the directory (another process
    sharing the store root) are not in this process's tally; its next
    scan lists and evicts them, oldest first."""
    d = _disk_tier_small(monkeypatch, tmp_path)
    ours = [_synthetic_put(0)]
    foreign = []
    for k in range(12):
        path = os.path.join(d, f"other-process-{k}.memo.pkl")
        open(path, "wb").close()
        os.utime(path, (0, 0))
        foreign.append(path)
    with obs.capture() as cap:
        for k in range(1, 10):
            ours.append(_synthetic_put(k))
    (ev,) = _evict_spans(cap.spans)
    assert ev["args"]["listed"] == 22 and ev["args"]["evicted"] == 14
    assert not any(os.path.exists(p) for p in foreign)
    assert sorted(os.listdir(d)) == sorted(os.path.basename(p)
                                           for p in ours[-8:])


def _evict_spans(spans):
    ev = _by_name(spans)["memo.disk.evict"]
    for e in ev:
        assert _ancestors(e, spans)[0] == "memo.disk.put"
    return ev


# -- serve roots ---------------------------------------------------------

def test_one_shot_request_spans_share_the_dispatch_root():
    import urllib.request

    from jepsen_tpu import serve

    d = serve.Daemon(port=0, host="127.0.0.1", group=1).start()
    try:
        hist = [op.to_dict() for op in fixtures.gen_history(
            "cas", n_ops=30, processes=3, seed=21)]
        req = urllib.request.Request(
            f"http://127.0.0.1:{d.port}/check",
            data=json.dumps({"model": "cas-register",
                             "history": hist}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            rid = json.loads(r.read())["id"]
        got = d.registry.get(rid)
        deadline = time.monotonic() + 120
        while not got.terminal and time.monotonic() < deadline:
            got.done_event.wait(0.05)
        assert got.status == "done" and got.result["valid"] is True
    finally:
        d.shutdown(drain_timeout=5.0)
    spans = core.GLOBAL.span_events()
    roots = [s for s in spans if s["name"] == "serve.dispatch"
             and rid in s["args"].get("reqs", ())]
    assert len(roots) == 1 and roots[0]["args"]["parent"] == 0
    rid_root = roots[0]["args"]["id"]
    prep = [s for s in spans if s["name"] == "reach.prep"
            and s["args"]["root"] == rid_root]
    assert len(prep) == 1
    assert "serve.dispatch" in _ancestors(prep[0], spans)
    walk = [s for s in spans if s["name"] == "reach.walk"
            and s["args"]["root"] == rid_root]
    assert walk and np.all([w["dur"] >= 0 for w in walk])
