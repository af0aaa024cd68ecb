"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (see repo build notes).
Must run before jax is imported anywhere."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent warm-start caches (jax compilation cache + disk memo tier)
# default OFF for the suite: they would litter ./store/.cache under the
# repo and couple test timings to disk state. Tests that cover
# persistence opt back in explicitly (monkeypatch.delenv + a tmp
# JEPSEN_TPU_CACHE_DIR, or a subprocess with its own env).
os.environ.setdefault("JEPSEN_TPU_NO_PERSIST", "1")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Pin jax's config too: a plugin imported before this file would have
# read the platform env var already.
import jax

jax.config.update("jax_platforms", "cpu")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests excluded from the tier-1 'not slow' run "
        "(e.g. the cas-100k obs acceptance rung)")


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """XLA-CPU's in-process LLVM JIT intermittently SEGFAULTs once a
    long single-process run has accumulated enough distinct compiled
    programs (observed twice at ~450 tests in jax's
    backend_compile_and_load; the fuzzer documents the same flake as
    'LLVM compilation error: Cannot allocate memory'). Dropping jax's
    executable/tracing caches at module boundaries keeps the resident
    program count bounded. Costs re-compiles of cross-module shared
    shapes — a few extra minutes over the suite — and nothing else:
    correctness never depends on a warm cache (the repo's cached jit
    factories hold only wrapper objects; their executables live in the
    global caches this drops)."""
    yield
    jax.clear_caches()
