"""The history-family hook moves no reading: each cell generates the
same histories and permutations, hands the program the same packed
arrays, counts the same work and samples the same checks as before the
hook existed. The digests below were computed with this file's
``digest`` on the tree before the hook (the drivers calling ``gen``,
``sut``, ``work`` and ``reference`` directly). The program's entry
points are stubbed: nothing compiles and no daemon starts.

    python3 -m pytest benchmark/tests/test_families.py -q
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import small  # noqa: E402

from benchmark import drivers, run, sut  # noqa: E402

SEED = 2**31 + 7
RUN_SECONDS = 20.0
CHECKS = {"check_packed": 17, "check_many": 2}


class _Run:
    """What a driver reads of :class:`run.Run`, from the cell's files,
    shrunk as ``small.SMALL`` shrinks them when ``shrink``."""

    def __init__(self, cell: str, shrink: bool):
        bench = run.load_json("BENCHMARK.json")
        self.cell = {w["name"]: w for w in bench["workloads"]}[cell]
        conf = {c["name"]: c for c in bench["configs"]}[self.cell["config"]]
        self.config = self._load(conf["file"], shrink)
        self.traffic = self._load(
            f"benchmark/traffic/{self.cell['traffic']}.json", shrink)
        self.seed, self.seconds, self.rate = SEED, RUN_SECONDS, None
        self.chips, self.devices = int(self.cell["chips"]), None

    @staticmethod
    def _load(path: str, shrink: bool) -> dict:
        d = run.load_json(path)
        if shrink:
            d.update(small.SMALL.get(
                os.path.splitext(os.path.basename(path))[0], {}))
        return d


class _Annotate:
    """The tracer's place in a window: no trace, cut after ``n`` checks."""

    def __init__(self, n: int):
        self.n, self.cut = n, False

    def __call__(self, _name: str):
        return contextlib.nullcontext()

    def tick(self, _t: float) -> None:
        self.n -= 1
        self.cut = self.n <= 0


class _Digest:
    def __init__(self):
        self.h = hashlib.sha256()

    def add(self, x) -> None:
        if isinstance(x, np.ndarray):
            self.h.update(f"{x.dtype}{x.shape}".encode())
            self.h.update(np.ascontiguousarray(x).tobytes())
        elif dataclasses.is_dataclass(x) and not isinstance(x, type) \
                and not hasattr(x, "distinct_ops"):
            for f in dataclasses.fields(x):
                self.add(f.name)
                self.add(getattr(x, f.name))
        elif hasattr(x, "distinct_ops"):            # a PackedHistory
            for k in ("n", "inv_ev", "ret_ev", "op_id", "crashed",
                      "inf_ev"):
                self.add(getattr(x, k))
            self.add(repr(x.distinct_ops))
            self.add(repr(x.op_keys))
            n = len(x.entries)
            self.add(n)
            for i in sorted({0, n // 2, n - 1}) if n else ():
                self.add(repr(x.entries[i]))
        elif isinstance(x, dict):
            for k in sorted(x, key=repr):
                self.add(repr(k))
                self.add(x[k])
        elif isinstance(x, (list, tuple)):
            self.add(f"{type(x).__name__}{len(x)}")
            for y in x:
                self.add(y)
        else:
            self.add_bytes(repr(x).encode())

    def add_bytes(self, b: bytes) -> None:
        self.h.update(len(b).to_bytes(8, "little"))
        self.h.update(b)


def _result(packed) -> dict:
    """A program answer that depends on what the program was handed."""
    n = int(packed.n)
    if n % 3:
        return {"valid": True, "engine": "reach-stub"}
    return {"valid": False, "engine": "reach-stub",
            "op": {"process": n % 5, "f": "read", "value": None,
                   "index": int(packed.inv_ev[-1])}}


def digest(cell: str, shrink: bool, monkeypatch) -> str:
    """sha256 over what the cell's driver generates, hands the program,
    counts and samples for the reference, with the program stubbed."""
    d = _Digest()
    seen: list = []

    def check_packed(packed):
        seen.append(packed)
        return _result(packed)

    def check_many(packed_list, devices=None, diag=None):
        seen.append(list(packed_list))
        return [_result(p) for p in packed_list]

    monkeypatch.setattr(sut, "check_packed", check_packed)
    monkeypatch.setattr(sut, "check_many", check_many)
    r = _Run(cell, shrink)
    driver = drivers.DRIVERS[r.traffic["entry"]](r)
    if r.traffic["entry"] == "serve":
        _serve(driver, monkeypatch, d)
    else:
        driver.setup()
        d.add(driver.bases)
        d.add(driver.work)
        driver.window(1e9, _Annotate(CHECKS[r.traffic["entry"]]))
        for rec in driver.records:
            d.add({k: v for k, v in rec.items()
                   if k not in ("t0", "t1", "diag")})
        if r.traffic["entry"] == "check_many":
            for order, perm, packed in driver.batches:
                d.add(order)
                d.add(perm)
                d.add(packed)
    d.add(seen)
    d.add(driver.checks())
    return d.h.hexdigest()


class _Daemon:
    port, registry = 1, {}

    def __init__(self, **_kw):
        pass

    def start(self):
        return self

    def shutdown(self, drain_timeout=None):
        pass


def _serve(driver, monkeypatch, d) -> None:
    """Set-up with the daemon stubbed (every warm-up body recorded),
    then the sample drawn over a stand-in list of window records."""
    from jepsen_tpu import serve
    posted: list = []
    monkeypatch.setattr(serve, "Daemon", _Daemon)
    monkeypatch.setattr(drivers, "_post",
                        lambda _url, body: posted.append(body))
    driver.setup()
    try:
        d.add([(t, b, h) for t, b, h in driver.reqs])
        d.add(driver.bad)
        d.add(posted)
        driver.records = [
            {"t_done": None if i % 7 == 3 else 1.0,
             "answer": (i % 2 == 0, None if i % 2 == 0 else
                        (i % 5, "read", "None", i))}
            for i in range(len(driver.reqs))]
    finally:
        driver.stop()


GOLDEN = {
    ("cas-register.long", True):
        "d9e487b7ff86bef03694662fa9d2f644a3634213eb6fc2341326ffac310a4fbd",
    ("cas-independent.batch", True):
        "e03f4218a74de33222138773926272ada769b40c60fa07db2bf39abcd55026aa",
    ("cas-register.serve", True):
        "d59461a5a49d5312c381006dec4f01e840b76977c58868fcf2d1e23814a33779",
    ("cas-register.long", False):
        "3dcf3d0019b0b0006edc755ee7ca00b78ccc03776ad7ae2822104b15b91db179",
    ("cas-independent.batch", False):
        "2ad861bd1f91855686b19f6a34293e081a2cf2ba7f3e51463080fcd77ccff6e6",
    ("cas-register.serve", False):
        "e13e310ab593df7f270ba3284d54ade0edadcacecd86311cf85640e26c147f15",
}


@pytest.mark.parametrize("cell,shrink", sorted(GOLDEN))
def test_cell_reproduces_its_digest(cell, shrink, monkeypatch):
    assert digest(cell, shrink, monkeypatch) == GOLDEN[(cell, shrink)]


# -- a family that a configuration brings as new files alone ------------

PROBE = "../tests/data/family_crash_probe"
PROBE_CELLS = {
    "crash-probe.long": ("crash-probe", "long"),
    "crash-probe-keyed.batch": ("crash-probe-keyed", "batch"),
    "crash-probe.serve": ("crash-probe", "serve"),
}
METRIC = {"long": "ops_per_s", "batch": "ops_per_s",
          "serve": "verdict_p50_s"}


def _probe_bench() -> dict:
    """``BENCHMARK.json`` with the probe's configurations and cells
    added, as a later change would add them."""
    bench = run.load_json("BENCHMARK.json")
    for name in sorted({c for c, _ in PROBE_CELLS.values()}):
        bench["configs"].append(
            {"name": name, "file": f"benchmark/tests/data/{name}.json"})
    metrics = {m["name"]: m for m in bench["end_to_end"]}
    for cell, (config, traffic) in PROBE_CELLS.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1})
        metrics[METRIC[traffic]]["workloads"].append(cell)
    return bench


def test_crash_probe_agrees_with_the_program():
    """Verdict and failing op, against ``reach.check_packed``, over
    seeded small histories with several crashed ops each, half of them
    corrupted."""
    from jepsen_tpu import models
    from jepsen_tpu.checkers import reach
    fam = drivers.load_family(PROBE)
    cfg = {"processes": 5, "values": 5, "crash_every": 6}
    wrong, crashed, invalid = [], 0, 0
    for seed in range(200):
        rng = np.random.default_rng(seed)
        h = fam.history(rng, cfg, (40, 60)[seed % 2])
        if seed % 4 >= 2:
            h = fam.corrupt_at(rng, h, None)
        got = sut.answer(reach.check_packed(models.cas_register(),
                                            fam.packer(h).packed(None)))
        ref = fam.check(h)
        crashed += int(h.crashed.sum())
        invalid += ref["valid"] is False
        if got != (ref["valid"], ref["op"]):
            wrong.append((seed, got, ref))
    assert wrong == []
    assert invalid == 100 and crashed >= 600


@pytest.mark.parametrize("cell", sorted(PROBE_CELLS))
def test_crash_probe_run_is_correct(cell):
    out = small.run_small(cell, seconds=1.5, bench=_probe_bench())
    assert out["correct"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("cell", sorted(PROBE_CELLS))
def test_crash_probe_control_is_not_correct(cell):
    out = small.run_small(cell, seconds=1.5, bench=_probe_bench(),
                          control=1)
    assert not out["correct"]
    assert out["compared"]["failing_op_mismatch"]["value"] > 0
