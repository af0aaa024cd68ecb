"""The partition deployment's history family
(``benchmark/families/cas_partition.py``) and its cell: the family's
reference agrees with the program, its histories hold what the
nemesis can make and no more, and the cell's small run is ``correct``
while its control is not. CPU only; minutes (the program's XLA walks
at up to 2**14 masks a key).

    python3 -m pytest benchmark/tests/test_partition.py -q
"""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import small  # noqa: E402

from benchmark import drivers, gen, run, sut  # noqa: E402

CELL = "cas-independent-partition.batch"
CONFIG = "benchmark/configs/cas-independent-partition.json"
SEED = 2**31 + 26


@pytest.fixture(scope="module")
def fam():
    return drivers.load_family("cas_partition")


@pytest.fixture(scope="module")
def cfg():
    return run.load_json(CONFIG)


def test_histories_hold_what_the_nemesis_makes(fam, cfg):
    """Per key at most one crashed op per minority thread (4), at most
    10 live and 4 crashed ops pending at once, no crashed read, and no
    process with two ops pending; over many keys the partition cuts
    about half of them and some reach W 13."""
    rng = np.random.default_rng(SEED)
    widths, crashed = [], []
    for _ in range(400):
        h = fam.history(rng, cfg, int(cfg["ops_per_key"]))
        c = int(h.crashed.sum())
        assert c <= 4
        assert not (h.crashed & (h.f == gen.READ)).any()
        assert not (h.crashed & ~np.isin(h.f, (gen.WRITE, gen.CAS))).any()
        w = fam.max_pending(h)
        assert w <= 10 + c
        live = h.ok | h.crashed
        for p in np.unique(h.proc[live]):
            ops = np.flatnonzero(live & (h.proc == p))
            open_ = h.crashed[ops]
            assert open_.sum() <= 1 and (not open_.any() or open_[-1])
        widths.append(w)
        crashed.append(c)
    assert 0.3 < np.mean(np.array(crashed) > 0) < 0.8
    assert max(widths) >= 13 and min(widths) <= 9


def test_reference_agrees_with_the_program(fam, cfg):
    """200 seeded histories, 100 of them corrupted: the family's
    reference and ``reach.check_packed`` give the same verdict and,
    on every invalid one, the same failing op."""
    rng = np.random.default_rng(SEED + 1)
    n_ops = int(cfg["small"]["ops_per_key"])
    n_crashed = 0
    for k in range(200):
        h = fam.history(rng, cfg, n_ops)
        if k % 2:
            h = fam.corrupt_at(rng, h, None)
        n_crashed += int(h.crashed.sum())
        ref = fam.check(h)
        assert ref["valid"] is (k % 2 == 0)
        got = sut.answer(fam.check_packed(fam.packer(h).packed(None)))
        assert got[0] is ref["valid"], k
        if not ref["valid"]:
            assert got[1] == ref["op"], k
    assert n_crashed >= 150


def test_small_run_is_correct_and_its_control_is_not():
    out = small.run_small(CELL)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] > 0
    ctl = small.run_small(CELL, control=1)
    assert not ctl["correct"]
    assert ctl["compared"]["failing_op_mismatch"]["value"] > 0


def test_mask_pad_share_reader():
    """100 x (1 - own rows / rows at the group's mask width) over the
    window's checks, padded lanes and returns left out; nothing to
    read where the program's groups carry no such rows (a program
    before the wide-M lanes)."""
    read = run.reader("mask_pad_share.keyed")
    ctx = {"records": [
        {"diag": {"groups": [{"rows": 9000, "mask_rows": 1000,
                              "own_rows": 250},
                             {"rows": 9000, "mask_rows": 3000,
                              "own_rows": 750}]}},
        {"diag": {"groups": [{"rows": 9000, "mask_rows": 4000,
                              "own_rows": 3000}]}}]}
    assert read(ctx) == pytest.approx(100.0 * (1 - 4000 / 8000))
    assert read({"records": [{"diag": {"groups": [{"H": 32}]}},
                             {"diag": {"groups": [{"rows": 10,
                                                   "own_rows": 5}]}},
                             {"diag": {}}]}) is None


_DEADLINE_PROBE = """
import sys, time
sys.path.insert(0, {root!r})
from benchmark import drivers, sut
fam = drivers.load_family("cas_partition")
fam.BATCH_DEADLINE_S = 1
sut.check_many = lambda packed_list, devices=None, diag=None: (
    time.sleep({hold}) or [])
fam.check_many([])
time.sleep(2)
print("answered")
"""


@pytest.mark.parametrize("hold,rc", [(0, 0), (30, 1)])
def test_a_batch_past_its_deadline_ends_the_run(hold, rc):
    """A batch answered in time leaves no timer behind; one with no
    verdict by the deadline ends the process at once, exit status 1,
    with the stacks on stderr."""
    import subprocess
    import time
    root = os.path.dirname(os.path.dirname(HERE))
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, "-c", _DEADLINE_PROBE.format(root=root, hold=hold)],
        capture_output=True, text=True, timeout=60)
    assert p.returncode == rc, p.stderr
    assert ("answered" in p.stdout) is (rc == 0)
    if rc:
        assert "Timeout" in p.stderr and time.monotonic() - t0 < 20
