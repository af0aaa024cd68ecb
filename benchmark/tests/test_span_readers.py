"""Unit tests of the readers of the program's parented spans
(`memo_share.keyed`, `witness_share.keyed`, `witness_share.single`,
`memo_p50_s.serve`) on synthetic contexts. CPU only, seconds.

    python3 -m pytest benchmark/tests -q
"""
from __future__ import annotations

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.metrics import _spans  # noqa: E402


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"),
        os.path.join(HERE, "..", "metrics", name + ".py"))
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m.read


def span(name, ts, dur, sid, parent=0, root=None, **args):
    return {"name": name, "ts": ts, "dur": dur,
            "args": dict(args, id=sid, parent=parent,
                         root=root if root is not None else sid)}


def keyed_ctx():
    # window [0, 1000] us; a witness [100, 500] holding a memo get
    # [120, 140], a build [140, 300] and a put [300, 420] with its
    # evict [310, 400]; a second witness [900, 1100] runs past the end
    spans = [span("bench.like", 0, 1000, 1),
             span("reach.witness", 100, 400, 2),
             span("memo.disk.get", 120, 20, 3, 2, 2),
             span("memo.build", 140, 160, 4, 2, 2),
             span("memo.disk.put", 300, 120, 5, 2, 2),
             span("memo.disk.evict", 310, 90, 6, 5, 2),
             span("union.stage_a", 600, 50, 7),
             span("memo.build", 610, 30, 8, 7, 7),
             span("reach.witness", 900, 200, 9)]
    return {"spans": spans, "obs_window_us": (0.0, 1000.0)}


def test_memo_and_witness_shares_are_unions_over_the_window():
    ctx = keyed_ctx()
    # memo: [120, 420] + [610, 640] = 330 us of 1000
    assert _reader("memo_share.keyed")(ctx) == pytest.approx(33.0)
    # witness: [100, 500] + [900, 1000] (clipped) = 500 us
    assert _reader("witness_share.keyed")(ctx) == pytest.approx(50.0)
    assert _reader("witness_share.single")(ctx) == pytest.approx(50.0)


def test_span_readers_read_zero_without_such_spans_and_none_on_old_spans():
    ctx = keyed_ctx()
    ctx["spans"] = [s for s in ctx["spans"]
                    if s["name"] != "reach.witness"]
    assert _reader("witness_share.single")(ctx) == 0.0
    # a program whose spans carry no ids predates these spans
    old = {"spans": [{"name": "reach.prep", "ts": 0, "dur": 5,
                      "args": {"ops": 3}},
                     {"name": "reach.walk", "ts": 5, "dur": 5}],
           "obs_window_us": (0.0, 10.0)}
    for name in ("memo_share.keyed", "witness_share.keyed",
                 "witness_share.single", "memo_p50_s.serve"):
        assert _reader(name)(dict(old, driver=None)) is None


def test_memo_p50_per_request_under_its_root():
    class D:
        sent = [{"id": "a"}, {"id": "b"}, {"id": "c"}, {"id": "gone"}]
    spans = [
        # request a: staged root 1 and collect root 10, memo 0.2 s + 0.1 s
        span("pipeline.stage", 0, 1e6, 1, reqs=["a", "b"]),
        span("reach.prep", 0, 5e5, 2, 1, 1),
        span("memo.build", 0, 2e5, 3, 2, 1),
        span("memo.disk.put", 1e5, 2e5, 4, 2, 1),   # overlaps the build
        span("serve.dispatch", 2e6, 1e6, 10, reqs=["a"]),
        span("memo.disk.get", 2e6, 1e5, 11, 10, 10),
        # request c: a root with no memo work
        span("serve.dispatch", 4e6, 1e6, 20, reqs=["c"]),
        span("reach.walk", 4e6, 1e6, 21, 20, 20),
        # another root's memo work is not request c's
        span("memo.build", 4e6, 9e5, 30),
    ]
    ctx = {"spans": spans, "driver": D()}
    per = _spans.per_request_s(ctx, _spans.is_memo)
    # a: [0, 0.3] s under root 1 + 0.1 s under root 10; b: 0.3 s; c: 0
    assert per == pytest.approx([0.4, 0.3, 0.0])
    assert _reader("memo_p50_s.serve")(ctx) == pytest.approx(0.3)
