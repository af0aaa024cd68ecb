"""Persistent warm-start caches (ISSUE 3): the jax compilation-cache
wiring (``store.enable_compilation_cache`` — ``JAX_COMPILATION_CACHE_DIR``
or one fixed directory in the checkout), the
disk-backed tier below ``reach._MEMO_CACHE`` with model-signature
invalidation, and the in-memory memo cache's LRU eviction order +
``memo_cache.*`` counters."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jepsen_tpu import fixtures, models, obs
from jepsen_tpu.checkers import reach
from jepsen_tpu.history import pack

_CHILD = r'''
import json, os
os.environ["JAX_PLATFORMS"] = "cpu"
from jepsen_tpu import obs, store
d = store.enable_compilation_cache()
import jax, jax.numpy as jnp
f = jax.jit(lambda x: (x @ x.T).sum() * {salt})
_ = float(f(jnp.arange(12.0).reshape(3, 4)))
c = obs.counters()
print(json.dumps({{"dir": d, "jax": jax.config.jax_compilation_cache_dir,
                   "hits": c.get("compile_cache.hits", 0),
                   "requests": c.get("compile_cache.requests", 0)}}))
'''


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JEPSEN_TPU_NO_PERSIST", None)   # conftest defaults it on
    env.update(extra_env or {})
    return env


def _run_child(tmp_path, salt, extra_env=None, jax_dir=True):
    env = _child_env({"JAX_COMPILATION_CACHE_DIR":
                      str(tmp_path / "xla"), **(extra_env or {})})
    if not jax_dir:
        env.pop("JAX_COMPILATION_CACHE_DIR")
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(salt=salt)],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=300)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_round_trip_across_processes(tmp_path):
    """A fresh process re-running the same computation hits the
    persistent compilation cache populated by the first, in the
    directory ``JAX_COMPILATION_CACHE_DIR`` names."""
    r1 = _run_child(tmp_path, 3)
    assert r1["dir"] == os.path.join(str(tmp_path), "xla")
    assert os.listdir(r1["dir"])             # cache populated
    assert r1["hits"] == 0
    r2 = _run_child(tmp_path, 3)
    assert r2["hits"] > 0                    # warm start skipped XLA


def test_compile_cache_opt_out(tmp_path):
    """JEPSEN_TPU_NO_PERSIST=1 disables the wiring entirely: no
    directory is set or created."""
    r = _run_child(tmp_path, 5, {"JEPSEN_TPU_NO_PERSIST": "1"},
                   jax_dir=False)
    assert r["dir"] is None and r["jax"] is None
    assert not (tmp_path / "xla").exists()


_DEFAULT_DIR_CHILD = r'''
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
from jepsen_tpu import store
store.create_run_dir({"store-root": sys.argv[1], "name": "t"})
import jax
print(json.dumps({"dir": store.enable_compilation_cache(),
                  "jax": jax.config.jax_compilation_cache_dir,
                  "fixed": store.XLA_CACHE_DIR}))
'''


def test_compile_cache_default_dir_is_fixed(tmp_path):
    """Without ``JAX_COMPILATION_CACHE_DIR`` the XLA tier lands in ONE
    directory inside the checkout, whatever the CWD or the run's store
    root (the path is part of jax's cache key: a directory that moves
    never hits)."""
    dirs = []
    for sub in ("a", "b"):
        cwd = tmp_path / sub
        cwd.mkdir()
        env = _child_env()
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        out = subprocess.run(
            [sys.executable, "-c", _DEFAULT_DIR_CHILD,
             str(tmp_path / f"store-{sub}")],
            capture_output=True, text=True, env=env, cwd=str(cwd),
            timeout=300)
        assert out.returncode == 0, out.stderr
        r = json.loads(out.stdout.strip().splitlines()[-1])
        assert r["dir"] == r["jax"] == r["fixed"]
        dirs.append(r["dir"])
    assert dirs[0] == dirs[1]
    assert dirs[0].startswith(_REPO + os.sep)


def _clear_memo_state():
    with reach._MEMO_CACHE_LOCK:
        reach._MEMO_CACHE.clear()
        reach._SUPERSET_SEEDS.clear()
        reach._SUPERSET_SEEDS_FAILED.clear()


def _persist_on(monkeypatch, tmp_path):
    monkeypatch.setenv("JEPSEN_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("JEPSEN_TPU_NO_PERSIST", raising=False)


def test_disk_memo_round_trip(tmp_path, monkeypatch):
    """A fresh memo-cache state (a new process, simulated by clearing
    the in-memory tiers) serves the memo from disk — identical table —
    and the counters record store/hit."""
    _persist_on(monkeypatch, tmp_path)
    model = models.cas_register()
    p = pack(fixtures.gen_history("cas", n_ops=60, processes=3, seed=7))
    _clear_memo_state()
    with obs.capture() as cap:
        m1 = reach._cached_memo(model, p, 100_000)
    assert cap.counters.get("memo_cache.disk.store") == 1
    assert cap.counters.get("memo_cache.miss") == 1
    _clear_memo_state()
    with obs.capture() as cap2:
        m2 = reach._cached_memo(model, p, 100_000)
    assert cap2.counters.get("memo_cache.disk.hit") == 1
    np.testing.assert_array_equal(m1.table, m2.table)
    assert m1.distinct_ops == m2.distinct_ops
    assert m1.initial == m2.initial
    # second lookup in the SAME process: in-memory hit, no disk I/O
    with obs.capture() as cap3:
        reach._cached_memo(model, p, 100_000)
    assert cap3.counters.get("memo_cache.hit") == 1
    assert "memo_cache.disk.hit" not in cap3.counters


def test_disk_memo_model_signature_invalidation(tmp_path, monkeypatch):
    """A changed model signature (different initial value, different
    max_states) can never serve a stale table."""
    _persist_on(monkeypatch, tmp_path)
    p = pack(fixtures.gen_history("cas", n_ops=60, processes=3, seed=7))
    _clear_memo_state()
    reach._cached_memo(models.cas_register(), p, 100_000)
    _clear_memo_state()
    with obs.capture() as cap:
        reach._cached_memo(models.cas_register(value=123), p, 100_000)
    assert "memo_cache.disk.hit" not in cap.counters
    _clear_memo_state()
    with obs.capture() as cap2:
        reach._cached_memo(models.cas_register(), p, 50_000)
    assert "memo_cache.disk.hit" not in cap2.counters
    # the original signature still hits
    _clear_memo_state()
    with obs.capture() as cap3:
        reach._cached_memo(models.cas_register(), p, 100_000)
    assert cap3.counters.get("memo_cache.disk.hit") == 1


def test_disk_memo_corrupt_entry_rebuilds(tmp_path, monkeypatch):
    """A truncated/corrupt disk entry is dropped and rebuilt, never
    trusted."""
    _persist_on(monkeypatch, tmp_path)
    model = models.cas_register()
    p = pack(fixtures.gen_history("cas", n_ops=40, processes=3, seed=9))
    _clear_memo_state()
    m1 = reach._cached_memo(model, p, 100_000)
    memo_dir = tmp_path / "memo"
    entries = list(memo_dir.iterdir())
    assert entries
    entries[0].write_bytes(b"not a pickle")
    _clear_memo_state()
    with obs.capture() as cap:
        m2 = reach._cached_memo(model, p, 100_000)
    assert cap.counters.get("memo_cache.disk.invalid") == 1
    np.testing.assert_array_equal(m1.table, m2.table)
    assert not entries[0].exists() or \
        entries[0].read_bytes() != b"not a pickle"


def test_disk_memo_skips_unstable_model_repr(tmp_path, monkeypatch):
    """A model with the default address-stamped repr has no stable
    cross-process signature: the disk tier must skip it entirely
    instead of minting one orphan entry per process."""
    _persist_on(monkeypatch, tmp_path)

    class Anon:
        pass                            # default <... object at 0x...> repr

    m = Anon()
    assert reach._disk_memo_path((m, 100_000, ())) is None
    # a stable repr still gets a path
    pr = reach._disk_memo_path((models.cas_register(), 100_000, ()))
    assert pr is not None and pr[0].endswith(".memo.pkl")


class _Sneaky(models.Model):
    """Module-level (picklable) model whose repr omits its behavior
    field — the repr-collision adversary of the disk memo tier."""

    def __init__(self, param):
        self.param = param

    def __repr__(self):
        return "Sneaky()"               # omits the behavior field

    def __eq__(self, other):
        return type(other) is _Sneaky and other.param == self.param

    def __hash__(self):
        return hash(("Sneaky", self.param))

    def step(self, op):
        return self


def test_disk_memo_repr_collision_rejected(tmp_path, monkeypatch):
    """Two UNEQUAL models sharing one repr (a custom __repr__ that
    omits a behavior field) must never serve each other's tables: the
    stored model object is compared by equality on load — the same
    relation the BFS keys states on."""
    _persist_on(monkeypatch, tmp_path)
    Sneaky = _Sneaky
    p = pack(fixtures.gen_history("cas", n_ops=30, processes=3, seed=2))
    reach._cached_memo(Sneaky(2), p, 1000)
    _clear_memo_state()
    with obs.capture() as cap:
        reach._cached_memo(Sneaky(3), p, 1000)
    assert "memo_cache.disk.hit" not in cap.counters
    assert cap.counters.get("memo_cache.disk.invalid") == 1
    _clear_memo_state()
    with obs.capture() as cap2:
        reach._cached_memo(Sneaky(3), p, 1000)   # truly equal: hits
    assert cap2.counters.get("memo_cache.disk.hit") == 1


def test_memo_cache_lru_not_insertion_order(monkeypatch):
    """Satellite: eviction is LRU — a hot memo inserted early survives
    a cold recent one — and memo_cache.{hit,miss,evict} count."""
    monkeypatch.setenv("JEPSEN_TPU_NO_PERSIST", "1")
    monkeypatch.setattr(reach, "_MEMO_CACHE_MAX", 2)
    _clear_memo_state()
    model = models.cas_register()
    # three distinct alphabets (different value sets → different sigs)
    ps = [pack(fixtures.gen_history("cas", n_ops=30 + 10 * i,
                                    processes=3, seed=100 + i))
          for i in range(3)]
    with obs.capture() as cap:
        reach._cached_memo(model, ps[0], 100_000)   # insert A
        reach._cached_memo(model, ps[1], 100_000)   # insert B (full)
        reach._cached_memo(model, ps[0], 100_000)   # hit A → MRU
        reach._cached_memo(model, ps[2], 100_000)   # insert C → evict B
        reach._cached_memo(model, ps[0], 100_000)   # A must still hit
    assert cap.counters.get("memo_cache.hit") == 2
    assert cap.counters.get("memo_cache.miss") == 3
    assert cap.counters.get("memo_cache.evict") == 1
    with obs.capture() as cap2:
        reach._cached_memo(model, ps[1], 100_000)   # B was evicted
    assert cap2.counters.get("memo_cache.miss") == 1
