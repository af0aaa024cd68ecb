"""Keyed lockstep lanes with crashed ops: each key's mask width W
(crashed slots held to the end) known before packing, groups that keep
keys of different mask cost apart, and the wide-M kernel that walks
the groups the one-array kernel's VMEM cannot hold — against the
per-key path, in interpret mode on the CPU."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from benchmark import gen  # noqa: E402
from jepsen_tpu import fixtures, models  # noqa: E402
from jepsen_tpu.checkers import preproc_native, reach, reach_batch  # noqa: E402
from jepsen_tpu.history import pack  # noqa: E402

needs_native = pytest.mark.skipif(
    not preproc_native.available(),
    reason="native preprocessing library unavailable")

# the keyed batch cell (benchmark/configs/cas-independent.json): keys,
# ops a key, processes, values, invalid keys, and a seed past 32 bits,
# as a benchmark run takes
BATCH_KEYS, BATCH_OPS, BATCH_PROCS, BATCH_VALUES = 4096, 100, 5, 5
CORRUPT_EVERY, CORRUPT_OFFSET, SEED = 8, 3, 2**31 + 7


def _batch_cell_keys():
    """Return counts and mask widths of the batch cell's keys, made as
    ``benchmark/drivers.py`` makes them."""
    rng = np.random.default_rng(SEED)
    lens, widths = [], []
    for k in range(BATCH_KEYS):
        h = gen.cas_history(rng, BATCH_OPS, BATCH_PROCS, BATCH_VALUES)
        if k % CORRUPT_EVERY == CORRUPT_OFFSET:
            h = gen.corrupt(rng, h)
        lens.append(int(h.ok.sum()))
        widths.append(gen.max_pending(h))
    return lens, widths


def test_batch_cell_groups_unchanged_by_key_widths():
    """The fault-free batch cell's keys all fit the one-array kernel:
    planning with their widths gives exactly the groups planning
    without them gives, and each group walks at the width it walked
    before (its widest key's), so at the same geometry."""
    lens, widths = _batch_cell_keys()
    assert max(widths) <= 9
    today = reach_batch.plan_buckets(lens, 20, group=32)
    assert reach_batch.plan_buckets(lens, 20, group=32, key_W=widths,
                                    S=8) == today
    assert all(len(g) == 32 for g in today)
    assert reach_batch.group_widths(today, widths, 8) == [
        max(widths[i] for i in g) for g in today]


def test_group_widths_one_per_class():
    """A wide class walks at its own W; every group of the class the
    one-array kernel holds walks at that class's widest W, whatever
    keys the group holds."""
    widths = [7, 8, 9, 8, 12, 12, 14, 7]
    groups = [[0, 1], [3, 7], [2], [4, 5], [6]]
    assert reach_batch.group_widths(groups, widths, 8) == [9, 9, 9, 12, 14]


def test_plan_buckets_keeps_mask_widths_apart():
    """A partition of every key; no group mixes a key the one-array
    kernel walks with a wider one, nor two wide widths; wide groups
    hold at most their geometry's lanes."""
    rng = np.random.default_rng(11)
    for _ in range(20):
        n = int(rng.integers(1, 300))
        lens = [int(x) for x in rng.integers(1, 3000, size=n)]
        widths = [int(x) for x in rng.integers(1, 16, size=n)]
        groups = reach_batch.plan_buckets(lens, 20, group=32,
                                          key_W=widths, S=8)
        assert sorted(i for g in groups for i in g) == list(range(n))
        for g in groups:
            wide = {reach_batch.lockstep_geometry(widths[i], 8).wide
                    for i in g}
            assert len(wide) == 1
            if wide == {True}:
                assert len({widths[i] for i in g}) == 1
                assert len(g) <= reach_batch.lockstep_geometry(
                    widths[g[0]], 8).H
            else:
                assert max(widths[i] for i in g) <= 9


def test_class_groups_share_one_geometry():
    """Keys of one length: each class's groups, split as evenly as can
    be, pad to one lane width (so one compiled program serves every
    group of the class, whichever keys a batch puts in which group)."""
    rng = np.random.default_rng(5)
    widths = [int(w) for w in rng.integers(7, 15, size=1875)]
    groups = reach_batch.plan_buckets([90] * len(widths), 20, group=32,
                                      key_W=widths, S=8)
    lanes: dict = {}
    for g, w in zip(groups, reach_batch.group_widths(groups, widths, 8)):
        lanes.setdefault(w, set()).add(-(-len(g) // 16) * 16)
    assert all(len(v) == 1 for v in lanes.values())
    assert lanes[9] == {32} and lanes[14] == {16}


@pytest.mark.parametrize("W", range(5, 16))
def test_geometry_inside_its_vmem_budget(W):
    """Every width the host gates pass at S=8 (up to 15) gets a
    geometry whose counted buffers, at the block the dispatch picks
    for it (``group_geom``), stay under the scoped limit the kernel
    asks for, and that limit under the cap; up to W=9 it is the
    fault-free batch's geometry (32 lanes, the one-array kernel,
    Mosaic's default limit), and the one-array kernel at 32 lanes is
    over the default limit from W=10, where AOT compiles for a v5e
    refuse it."""
    S = 8
    geo = reach_batch.lockstep_geometry(W, S)
    assert geo is not None
    B, _R_pad = reach_batch.group_geom(100, geo.H, W)
    used = reach_batch.vmem_bytes(W, S, geo.H, B, geo.wide)
    if W <= 9:
        assert geo == reach_batch.Geometry(32, False)
        assert used <= reach_batch._SCOPED_VMEM
    else:
        assert geo.wide and geo.H * S == 128
        assert used <= reach_batch._wide_vmem_limit(W, S, geo.H, B) \
            <= reach_batch._VMEM_CAP
        legacy = reach_batch.vmem_bytes(
            W, S, 32, reach_batch._adaptive_block(32, W), False)
        assert legacy > reach_batch._SCOPED_VMEM


def test_width_past_the_cap_is_refused_by_the_gates():
    """Past the cap no kernel fits: the group is not sent to the
    wide-M kernel, and its gates refuse it before any compile."""
    assert reach_batch.lockstep_geometry(20, 8) is None
    assert reach_batch._wide_tile(20, 1 << 20, 8) == 0
    assert not reach._lockstep_fits(8, 20, 1 << 20, 37)


def _crashy_packs():
    """Crash-free keys and keys with 1-3 crashed ops, two invalid."""
    packs = []
    for i, (n, crash_p, seed) in enumerate([
            (30, 0.0, 3000), (36, 0.03, 3001), (28, 0.0, 3002),
            (40, 0.04, 3004), (34, 0.03, 3002), (26, 0.0, 3005)]):
        h = fixtures.gen_history("cas", n_ops=n, processes=3,
                                 seed=seed, crash_p=crash_p)
        if i in (1, 2):
            h = fixtures.corrupt(h, seed=i)
        packs.append(pack(h))
    return packs


@needs_native
def test_stage_a_widths_match_the_native_build():
    """Stage A's per-key widths, from the event ranks alone, are the
    widths the native build gives each key."""
    packs = _crashy_packs()
    live = list(range(len(packs)))
    sa = reach._union_stage_a(models.cas_register(), packs, live, 100_000)
    built = reach._union_pack_group(sa, live, 20)
    assert list(sa.key_W) == list(built[2])
    assert max(sa.key_W) > min(sa.key_W)


@needs_native
@pytest.mark.parametrize("entry", ["check_many", "check_batch",
                                   "stage_check_many"])
def test_wide_groups_match_per_key(monkeypatch, entry):
    """A mixed batch with crashed ops through the keyed lockstep lane,
    from each of its entry points (the staged one collected at once),
    the one-array kernel's budget cut so that keys of W >= 4 walk the
    wide-M kernel (in 4-row tiles, so both its within-tile and
    across-tile steps run): verdicts, dead events and failing ops
    equal the per-key path's, and every key is answered by
    ``reach-lockstep``."""
    packs = _crashy_packs()
    assert 1 <= max(int(p.crashed.sum()) for p in packs) <= 3
    refs = [reach.check_packed(models.cas_register(), p) for p in packs]
    budget = reach_batch.vmem_bytes
    monkeypatch.setattr(
        reach_batch, "vmem_bytes",
        lambda W, S, H, B, wide, O1=64:
        budget(W, S, H, B, wide, O1) if wide or W < 4 else 1 << 40)
    monkeypatch.setattr(reach_batch, "_TILE_ROWS", 4)
    monkeypatch.setattr(reach, "_use_pallas", lambda: True)
    monkeypatch.setattr(reach, "_PALLAS_MIN_RETURNS", 0)
    monkeypatch.setattr(reach_batch, "_INTERPRET_DEFAULT", True)
    model = models.cas_register()
    diag = {}
    if entry == "stage_check_many":
        staged = reach.stage_check_many(model, packs)
        assert isinstance(staged, reach.StagedMany)
        res = staged.collect()
        gdiags = staged.gdiags
    else:
        res = getattr(reach, entry)(model, packs, diag=diag)
        gdiags = diag["groups"]
    assert all(r["engine"] == "reach-lockstep" for r in res)
    widths = [g["W"] for g in gdiags]
    assert min(widths) < 4 <= max(widths)
    for g in gdiags:
        assert 0 < g["own_rows"] <= g["mask_rows"] <= g["rows"]
        assert g["mask_rows"] == g["M"] * g["real_returns"]
    for i, (a, b) in enumerate(zip(res, refs)):
        assert a["valid"] == b["valid"], f"key {i}"
        if a["valid"] is False:
            assert a["dead-event"] == b["dead-event"], f"key {i}"
            assert a["op"] == b["op"], f"key {i}"


@pytest.mark.parametrize("lanes,W,H", [(28, 5, 32), (32, 5, 32),
                                       (10, 5, 10), (5, 12, 16)])
def test_short_groups_fill_whole_lane_tiles(lanes, W, H):
    """A bf16 group whose lanes do not fill whole 128-lane tiles (Mosaic
    refuses the mask reshapes at 28 lanes x 8 states, AOT for a v5e),
    and every wide-M group, walks pad lanes up to the tile; a group
    below one tile walks f32 as it is. Verdicts come back for the real
    lanes only."""
    S, O1 = 8, 4
    P = np.zeros((O1, S, S), np.float32)
    rets = [np.zeros(3, np.int32) for _ in range(lanes)]
    ops = [np.full((3, W), -1, np.int32) for _ in range(lanes)]
    for o in ops:
        o[:, 0] = 0
    geom, _args, R_lens = reach_batch.pack_batch_operands(
        P, rets, ops, 1 << W, interpret=True)
    assert geom[4] == H and len(R_lens) == lanes
