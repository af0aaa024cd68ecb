"""Sparse batched-frontier engine tests: hand-written verdicts,
differential agreement with the CPU WGL oracle (including crash-heavy
histories), the crashed-op interchangeability quotient beating the exact
CPU searches, capacity-overflow and abort behaviour, and the facade's
auto-fallback routing."""
import numpy as np
import pytest

from jepsen_tpu import fixtures
from jepsen_tpu import models as m
from jepsen_tpu.checkers import facade, frontier, wgl_native, wgl_ref
from jepsen_tpu.history import index
from jepsen_tpu.op import info, invoke, ok


@pytest.fixture(autouse=True)
def _sparse_path(monkeypatch):
    """These tests target the SPARSE frontier machinery; the round-3
    dense product-space fast path (reach_q) has its own suite
    (tests/test_reach_q.py) and would otherwise absorb most cases."""
    monkeypatch.setenv("JEPSEN_TPU_NO_QUOTIENT", "1")


def hist(*ops):
    return index(list(ops))


def crash_heavy(n_crashed=24, n_live=20, value=1):
    """``n_crashed`` processes invoke write(value) and never return, with a
    successful read(0) interleaved after each crash; a live process then
    does read/write traffic. Valid, but the crashed writes share one op id
    — the interleaved reads make the exact searches reach ~2**n_crashed
    distinct linearized subsets (config-set explosion for C++ WGL), while
    the quotient keeps ~n_crashed+1 canonical configs."""
    h = [invoke(0, "write", 0), ok(0, "write", 0)]
    for c in range(n_crashed):
        h += [invoke(100 + c, "write", value), info(100 + c, "write", value),
              invoke(0, "read"), ok(0, "read", 0)]
    for i in range(n_live):
        v = i % 3
        h += [invoke(0, "write", v), ok(0, "write", v),
              invoke(0, "read"), ok(0, "read", v)]
    return index(h)


class TestHandWritten:
    def test_empty_valid(self):
        assert frontier.check(m.register(), [])["valid"] is True

    def test_sequential_rw_valid(self):
        h = hist(
            invoke(0, "write", 1), ok(0, "write", 1),
            invoke(0, "read"), ok(0, "read", 1),
        )
        res = frontier.check(m.register(), h, frontier0=64)
        assert res["valid"] is True
        assert res["engine"] == "frontier"

    def test_stale_read_invalid_with_evidence(self):
        h = hist(
            invoke(0, "write", 1), ok(0, "write", 1),
            invoke(0, "write", 2), ok(0, "write", 2),
            invoke(0, "read"), ok(0, "read", 1),
        )
        res = frontier.check(m.register(), h, frontier0=64)
        assert res["valid"] is False
        assert res["op"]["f"] == "read"
        assert res["op"]["value"] == 1
        assert res["previous-ok"]["f"] == "write"
        assert res["previous-ok"]["value"] == 2
        assert len(res["final-configs"]) >= 1
        assert any("2" in c["model"] for c in res["final-configs"])

    def test_crashed_write_both_branches(self):
        base = [
            invoke(0, "write", 1), ok(0, "write", 1),
            invoke(1, "write", 2), info(1, "write", 2),
            invoke(0, "read"),
        ]
        ok_seen = frontier.check(m.register(),
                                 hist(*base, ok(0, "read", 2)),
                                 frontier0=64)
        ok_unseen = frontier.check(m.register(),
                                   hist(*base, ok(0, "read", 1)),
                                   frontier0=64)
        assert ok_seen["valid"] is True
        assert ok_unseen["valid"] is True


class TestDifferential:
    @pytest.mark.parametrize("kind", ["register", "cas", "mutex"])
    def test_agrees_with_oracle_crash_heavy(self, kind):
        for seed in range(4):
            h = fixtures.gen_history(kind, n_ops=30, processes=3, values=3,
                                     crash_p=0.2, seed=seed)
            model = fixtures.model_for(kind)
            ref = wgl_ref.check(model, h)
            got = frontier.check(model, h, frontier0=64)
            assert got["valid"] == ref["valid"], (kind, seed)

    def test_agrees_on_corrupted(self):
        for seed in range(3):
            h = fixtures.gen_history("cas", n_ops=40, processes=3,
                                     seed=seed)
            hb = fixtures.corrupt(h, seed=seed)
            got = frontier.check(m.cas_register(), hb, frontier0=64)
            assert got["valid"] is False

    def test_fixture_files(self):
        import os

        from jepsen_tpu import history as H
        data = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "data")
        for name, model, want in [
            ("register-ok.edn", m.register(), True),
            ("register-bad.edn", m.register(), False),
            ("cas-register-ok-small.edn", m.cas_register(), True),
            ("cas-register-bad.edn", m.cas_register(), False),
        ]:
            h = H.load_edn(os.path.join(data, name))
            res = frontier.check(model, h, frontier0=64)
            assert res["valid"] is want, name


class TestCrashedOpQuotient:
    def test_collapses_same_id_crashes(self):
        """24 same-id crashed writes: 2**24 linearized subsets for an
        un-quotiented exact search, ~25 canonical configs here (the C++
        engine's DFS form of the same quotient is covered in
        test_wgl_native.py)."""
        h = crash_heavy()
        res = frontier.check(m.register(), h, frontier0=64)
        assert res["valid"] is True
        assert res["slots"] >= 24
        assert res["frontier-cap"] <= 256

    def test_quotient_does_not_merge_live_ops(self):
        """Two concurrent pending writes of the SAME value, one crashed
        and one live: the live op's return must still require its own
        linearization (a quotient that grouped live with crashed would
        wrongly accept firing only the crashed one)."""
        h = hist(
            invoke(0, "write", 0), ok(0, "write", 0),
            invoke(1, "write", 1), info(1, "write", 1),     # crashed
            invoke(2, "write", 1),                          # live, pending
            invoke(3, "read"), ok(3, "read", 1),
            ok(2, "write", 1),                              # live returns
            invoke(3, "write", 2), ok(3, "write", 2),
            invoke(3, "read"), ok(3, "read", 1),  # stale: needs BOTH writes
        )
        res = frontier.check(m.register(), h, frontier0=64)
        ref = wgl_ref.check(m.register(), h)
        assert res["valid"] == ref["valid"]

    def test_distinct_values_not_merged(self):
        """Crashed writes of DIFFERENT values are different op ids and
        must stay distinct configs."""
        h = hist(
            invoke(0, "write", 0), ok(0, "write", 0),
            invoke(1, "write", 1), info(1, "write", 1),
            invoke(2, "write", 2), info(2, "write", 2),
            invoke(3, "read"), ok(3, "read", 1),
            invoke(3, "read"), ok(3, "read", 2),
            invoke(3, "read"), ok(3, "read", 1),   # 1 after 2: impossible
        )
        res = frontier.check(m.register(), h, frontier0=64)
        assert res["valid"] is False


class TestCrashedSlotScan:
    def test_vectorized_matches_reference(self):
        from jepsen_tpu.checkers import events as ev
        from jepsen_tpu.checkers import reach
        from jepsen_tpu.history import pack

        for seed in range(6):
            h = fixtures.gen_history("cas", n_ops=50, processes=4,
                                     values=3, crash_p=0.25, seed=seed)
            packed = pack(h)
            memo = reach._cached_memo(m.cas_register(), packed, 100_000)
            stream = ev.build(packed, memo, max_slots=frontier.MAX_SLOTS)
            W = max(stream.W, 1)
            got = frontier._crashed_slots(stream, packed, W)
            ref = frontier._crashed_slots_ref(stream, packed, W)
            assert np.array_equal(got, ref), seed


class TestLimits:
    def test_frontier_overflow_raises(self):
        # distinct-value crashed CAS ops: the quotient cannot collapse
        # them, so a tiny capacity must overflow
        h = [invoke(0, "write", 0), ok(0, "write", 0)]
        for c in range(10):
            h += [invoke(100 + c, "cas", (c % 5, (c + 1) % 5)),
                  info(100 + c, "cas", (c % 5, (c + 1) % 5))]
        for i in range(6):
            h += [invoke(0, "write", i % 5), ok(0, "write", i % 5)]
        with pytest.raises(frontier.FrontierOverflow):
            frontier.check(m.cas_register(), index(h), frontier0=64,
                           max_frontier=64)

    def test_abort_returns_unknown(self):
        h = fixtures.gen_history("cas", n_ops=30, processes=3, seed=0)
        res = frontier.check(m.cas_register(), h, frontier0=64,
                             should_abort=lambda: True)
        assert res["valid"] == "unknown"
        assert res["cause"] == "aborted"


class TestSharded:
    """Mesh-sharded walk on the conftest-forced 8-device CPU mesh: config
    rows hash-route to owner shards (all_to_all), so local dedup is
    global dedup."""

    def _devs(self):
        import jax
        return jax.devices()

    def test_agrees_with_single_device(self):
        devs = self._devs()
        if len(devs) < 2:
            pytest.skip("needs a multi-device mesh")
        for seed in range(3):
            h = fixtures.gen_history("register", n_ops=40, processes=4,
                                     values=3, crash_p=0.15, seed=seed)
            model = m.register()
            single = frontier.check(model, h, frontier0=256)
            sharded = frontier.check(model, h, frontier0=256, devices=devs)
            assert sharded["valid"] == single["valid"], seed

    def test_invalid_with_witness(self):
        devs = self._devs()
        if len(devs) < 2:
            pytest.skip("needs a multi-device mesh")
        h = fixtures.gen_history("cas", n_ops=60, processes=5, seed=1)
        hb = fixtures.corrupt(h, seed=1)
        res = frontier.check(m.cas_register(), hb, frontier0=256,
                             devices=devs)
        assert res["valid"] is False
        assert "op" in res

    def test_escalation_and_overflow(self):
        devs = self._devs()
        if len(devs) < 2:
            pytest.skip("needs a multi-device mesh")
        h = fixtures.gen_history("register", n_ops=40, processes=4,
                                 values=3, crash_p=0.2, seed=5)
        res = frontier.check(m.register(), h, frontier0=64, devices=devs)
        assert res["valid"] is True
        hh = [invoke(0, "write", 0), ok(0, "write", 0)]
        for c in range(10):
            hh += [invoke(100 + c, "cas", (c % 5, (c + 1) % 5)),
                   info(100 + c, "cas", (c % 5, (c + 1) % 5))]
        for i in range(6):
            hh += [invoke(0, "write", i % 5), ok(0, "write", i % 5)]
        with pytest.raises(frontier.FrontierOverflow):
            frontier.check(m.cas_register(), index(hh), frontier0=64,
                           max_frontier=512, devices=devs)

    def test_host_device_hash_agree(self):
        import jax.numpy as jnp
        rng = np.random.default_rng(0)
        rows = rng.integers(0, 2**32, size=(64, 3), dtype=np.uint32)
        host = frontier._hash_rows_np(rows, 8)
        dev = np.asarray(frontier._hash_rows(jnp.asarray(rows), 8))
        assert np.array_equal(host, dev)


class TestFacadeRouting:
    def test_explicit_algorithm(self):
        h = fixtures.gen_history("register", n_ops=20, processes=3, seed=1)
        res = facade.linearizable(m.register(),
                                  algorithm="frontier",
                                  frontier0=64).check(None, h)
        assert res["valid"] is True
        assert res["engine"] == "frontier"

    def test_auto_falls_back_to_frontier(self):
        """>20 pending slots (dense engine overflows) with a TWO-value
        crashed-op pile-up: the quotient class is ~13x13 wide, so the C++
        search's CUMULATIVE memo blows a tight config budget while the
        frontier's PER-RETURN width fits easily — auto must still produce
        a definitive verdict via the frontier engine."""
        h = [invoke(0, "write", 0), ok(0, "write", 0)]
        for c in range(24):
            v = 1 + (c % 2)
            h += [invoke(100 + c, "write", v), info(100 + c, "write", v),
                  invoke(0, "read"), ok(0, "read", 0)]
        for i in range(20):
            v = i % 3
            h += [invoke(0, "write", v), ok(0, "write", v),
                  invoke(0, "read"), ok(0, "read", v)]
        res = facade.linearizable(
            m.register(), max_configs=1000,
            frontier0=64).check(None, index(h))
        assert res["valid"] is True
        assert res["engine"] in ("frontier-fallback", "frontier")


class TestBigFrontier:
    def test_65536_row_frontier(self):
        """The full walk at F=65536 — dedup sorts of ~590k rows, the
        shape that crashed round 1's remote TPU worker (the default
        max_frontier is no longer tuned to that). Runs at full
        capacity from the start so every segment exercises the big
        sort."""
        h = fixtures.gen_history("register", n_ops=40, processes=3,
                                 crash_p=0.1, values=3, seed=7)
        res = frontier.check(m.register(), h, frontier0=1 << 16,
                             max_frontier=1 << 17)
        assert res["valid"] is True
        ref = wgl_ref.check(m.register(), h)
        assert ref["valid"] is True

    def test_default_cap_is_lifted(self):
        import inspect
        sig = inspect.signature(frontier.check)
        assert sig.parameters["max_frontier"].default >= 1 << 17
