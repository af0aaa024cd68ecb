"""Result persistence — upstream ``jepsen/src/jepsen/store.clj``
(SURVEY.md §2.1, L9): ``store/<test-name>/<timestamp>/`` directories with
the serialized test, history, results, and logs, plus a ``latest`` symlink.

The upstream serializes with fressian (JVM binary); here the formats are
JSONL for histories (crash-safe, append-only — written live by
:class:`jepsen_tpu.core.History`), JSON for results, and EDN exports for
interop with upstream tooling (``history.edn`` readable by real Jepsen /
knossos and vice versa via :func:`jepsen_tpu.history.load_edn`).
"""
from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, List, Mapping, Optional

from jepsen_tpu import edn
from jepsen_tpu import history as h
from jepsen_tpu.op import Op

log = logging.getLogger("jepsen.store")

# keys that are live objects, not data — skipped when serializing the test
# map (the upstream stores fressian handlers for these; we store repr)
_LIVE_KEYS = ("client", "db", "os", "net", "nemesis", "generator", "checker",
              "model", "remote", "cluster", "active-processes", "history",
              "results")


# -- persistent warm-start caches ------------------------------------------
#
# Fresh processes re-paid XLA compilation for every kernel geometry and
# re-ran the memo BFS for every alphabet (ISSUE 3). Two tiers:
#
# - XLA: jax's persistent compilation cache. Where the environment sets
#   ``JAX_COMPILATION_CACHE_DIR`` that setting stands and nothing here
#   picks a directory; otherwise one fixed directory inside the
#   checkout (:data:`XLA_CACHE_DIR`), whatever the CWD or store root —
#   the path is part of the cache key, so a moving directory never hits.
# - memo/autotune: ``<store-root>/.cache`` (``JEPSEN_TPU_CACHE_DIR``
#   relocates it).
#
# ``JEPSEN_TPU_NO_PERSIST=1`` disables both.

_PERSIST_STATE: Dict[str, Any] = {}

XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".cache", "xla")


def persist_root(store_root: Optional[str] = None) -> Optional[str]:
    """Root directory of the memo and autotune caches, or None when
    persistence is disabled (``JEPSEN_TPU_NO_PERSIST=1``). Defaults to
    ``<store-root>/.cache`` — keyed under the store dir so the caches
    travel with the runs they warmed — overridable via
    ``JEPSEN_TPU_CACHE_DIR``. With no explicit ``store_root``, the
    last run dir's root (:func:`create_run_dir`) applies. Env is
    consulted per call (tests toggle it at runtime)."""
    if os.environ.get("JEPSEN_TPU_NO_PERSIST"):
        return None
    d = os.environ.get("JEPSEN_TPU_CACHE_DIR")
    if d:
        return d
    root = store_root or _PERSIST_STATE.get("root") or "store"
    return os.path.join(root, ".cache")


def enable_compilation_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache so rechecks and fresh
    processes skip XLA recompiles of every kernel geometry they have
    seen before: in ``JAX_COMPILATION_CACHE_DIR`` where that is set,
    else in :data:`XLA_CACHE_DIR`. Idempotent and best-effort (a
    read-only filesystem must never fail a check); returns the cache
    dir, or None when disabled or unavailable. The compile-time floor
    is dropped to 0 — the walks compile MANY small per-geometry
    programs whose aggregate recompile cost is the warm-start wall this
    hides — and the tier is bounded at 1 GiB (fuzz/soak mint fresh
    geometries forever; jax evicts LRU past the bound)."""
    if os.environ.get("JEPSEN_TPU_NO_PERSIST"):
        return None
    if "cc_dir" in _PERSIST_STATE:
        return _PERSIST_STATE["cc_dir"]
    try:
        import jax
        d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not d:
            d = XLA_CACHE_DIR
            os.makedirs(d, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", d)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          -1)
        jax.config.update("jax_compilation_cache_max_size", 1 << 30)
        _install_compile_cache_metrics()
        _PERSIST_STATE["cc_dir"] = d
        return d
    except Exception as e:                              # noqa: BLE001
        log.warning("persistent compilation cache unavailable: %s", e)
        return None


def _install_compile_cache_metrics() -> None:
    """Translate jax's compilation-cache monitoring events into obs
    counters (``compile_cache.hits`` / ``compile_cache.requests``) so
    bench runs and stored ``obs.jsonl`` show whether a warm start
    actually skipped recompiles. Best-effort."""
    if _PERSIST_STATE.get("metrics"):
        return
    try:
        from jax import monitoring

        from jepsen_tpu import obs

        def _on_event(event: str, **kw: Any) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                obs.count("compile_cache.hits")
            elif event == "/jax/compilation_cache/compile_requests_use_cache":
                obs.count("compile_cache.requests")

        monitoring.register_event_listener(_on_event)
        _PERSIST_STATE["metrics"] = True
    except Exception:                                   # noqa: BLE001
        pass


def create_run_dir(test: Mapping) -> str:
    root = test.get("store-root", "store")
    # re-key the memo/autotune caches under THIS run's store root (a
    # test configured with store-root=/data/runs must not leave its
    # warm artifacts under ./store/.cache of whatever CWD the process
    # has); the XLA tier stays at its one fixed directory
    _PERSIST_STATE["root"] = root
    enable_compilation_cache()
    name = str(test.get("name", "test")).replace("/", "_")
    ts = test.get("start-time") or "run"
    d = os.path.join(root, name, ts)
    n = 0
    base = d
    while os.path.exists(d):
        n += 1
        d = f"{base}-{n}"
    os.makedirs(d, exist_ok=True)
    _symlink_latest(os.path.join(root, name), d)
    _symlink_latest(root, d)
    return d


def _symlink_latest(parent: str, target: str) -> None:
    link = os.path.join(parent, "latest")
    try:
        if os.path.islink(link):
            os.unlink(link)
        os.symlink(os.path.relpath(target, parent), link)
    except OSError:                                     # e.g. on Windows
        pass


def attach_log(run_dir: str) -> logging.Handler:
    """Tee the jepsen logger into ``<dir>/jepsen.log`` (upstream logback
    config writes the same file). Returns the handler; callers must pass
    it to :func:`detach_log` when the run ends or handlers accumulate
    across runs in one process."""
    handler = logging.FileHandler(os.path.join(run_dir, "jepsen.log"))
    handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s [%(name)s] %(message)s"))
    logging.getLogger("jepsen").addHandler(handler)
    return handler


def detach_log(handler: logging.Handler) -> None:
    logging.getLogger("jepsen").removeHandler(handler)
    handler.close()


def _serializable_test(test: Mapping) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in test.items():
        if k in _LIVE_KEYS:
            if v is not None:
                out[k] = repr(v)
        else:
            try:
                json.dumps(v)
                out[k] = v
            except (TypeError, ValueError):
                out[k] = repr(v)
    return out


def save(test: Mapping, run_dir: Optional[str] = None) -> str:
    """Persist a completed test (upstream ``store/save!``): ``test.json``,
    ``results.json`` + ``results.edn``, ``history.jsonl`` (if not already
    streamed), ``history.edn``, ``history.txt``."""
    run_dir = run_dir or test.get("dir") or create_run_dir(test)
    history: List[Op] = test.get("history", [])

    with open(os.path.join(run_dir, "test.json"), "w") as f:
        json.dump(_serializable_test(test), f, indent=2, default=str)

    results = test.get("results", {})
    with open(os.path.join(run_dir, "results.json"), "w") as f:
        json.dump(results, f, indent=2, default=str)
    with open(os.path.join(run_dir, "results.edn"), "w") as f:
        f.write(edn.dumps(results) + "\n")

    jsonl = os.path.join(run_dir, "history.jsonl")
    if not os.path.exists(jsonl):
        h.save_jsonl(history, jsonl)
    h.save_edn(history, os.path.join(run_dir, "history.edn"))
    with open(os.path.join(run_dir, "history.txt"), "w") as f:
        for op in history:
            f.write(f"{op.process}\t{op.type}\t{op.f}\t{op.value!r}\n")
    return run_dir


def save_obs(run_dir: str, capture: Optional[Any] = None) -> None:
    """Persist the run's observability record next to its history:
    ``obs.jsonl`` (spans + counters + engine-decision ledger, one JSON
    object per line) and ``trace.json`` (Chrome/Perfetto
    ``trace_event`` — load in ``chrome://tracing`` or ui.perfetto.dev;
    summarize with ``tools/trace_view.py``). ``capture`` is the run's
    :class:`jepsen_tpu.obs.Capture` (None exports the process-global
    recorder). Best-effort: persistence failures must never fail a
    completed run."""
    from jepsen_tpu import obs
    try:
        obs.export_jsonl(os.path.join(run_dir, "obs.jsonl"), capture)
        obs.export_trace(os.path.join(run_dir, "trace.json"), capture)
    except Exception as e:                              # noqa: BLE001
        log.warning("obs persistence failed: %s", e)


def save_check(root: str, name: str, run_id: str, history: List[Op],
               results: Mapping) -> str:
    """Persist one standalone check (the check-serve daemon's unit of
    work) as a browsable run dir — ``<root>/<name>/<ts>-<run_id>/``.
    Delegates to :func:`save` so daemon runs carry the exact artifact
    set CLI runs do (``results.json``/``.edn``, ``history.jsonl``/
    ``.edn``/``.txt``, ``test.json``) and cannot drift from it."""
    import time as _time
    ts = _time.strftime("%Y%m%dT%H%M%S", _time.gmtime())
    d = os.path.join(root, str(name).replace("/", "_"),
                     f"{ts}-{run_id}")
    os.makedirs(d, exist_ok=True)
    return save({"name": name, "history": list(history),
                 "results": results}, run_dir=d)


def serve_journal_dir(root: str) -> str:
    """The check-serve daemon's durable admission journal —
    ``<root>/serve/journal/``, beside its ``stats.json`` and profile
    captures: the WAL of admitted requests that makes the daemon's
    202s survive SIGKILL (see :mod:`jepsen_tpu.serve.journal`)."""
    d = os.path.join(root, "serve", "journal")
    os.makedirs(d, exist_ok=True)
    return d


def serve_profile_dir(root: str) -> str:
    """Create (and return) a fresh capture directory for the
    check-serve daemon's on-demand profiler —
    ``<root>/serve/profile-<ts>/``, beside the daemon's
    ``stats.json`` so captures are browsable artifacts of the store
    like everything else the daemon writes."""
    import time as _time
    ts = _time.strftime("%Y%m%dT%H%M%S", _time.gmtime())
    d = os.path.join(root, "serve", f"profile-{ts}")
    n = 0
    base = d
    while os.path.exists(d):
        n += 1
        d = f"{base}-{n}"
    os.makedirs(d, exist_ok=True)
    return d


def load_history(run_dir: str) -> List[Op]:
    """Load a stored history for offline re-analysis (the upstream
    re-check path; SURVEY.md §5 checkpoint/resume)."""
    jsonl = os.path.join(run_dir, "history.jsonl")
    if os.path.exists(jsonl):
        return h.load_jsonl(jsonl)
    p = os.path.join(run_dir, "history.edn")
    if os.path.exists(p):
        return h.load_edn(p)
    raise FileNotFoundError(f"no history in {run_dir}")


def load_results(run_dir: str) -> Dict[str, Any]:
    with open(os.path.join(run_dir, "results.json")) as f:
        return json.load(f)


def tests(root: str = "store") -> Dict[str, List[str]]:
    """Map test name → sorted run dirs (upstream ``store/tests``)."""
    out: Dict[str, List[str]] = {}
    if not os.path.isdir(root):
        return out
    for name in sorted(os.listdir(root)):
        d = os.path.join(root, name)
        if name == "latest" or not os.path.isdir(d):
            continue
        runs = sorted(
            os.path.join(d, r) for r in os.listdir(d)
            if r != "latest" and os.path.isdir(os.path.join(d, r)))
        if runs:
            out[name] = runs
    return out


def latest(root: str = "store") -> Optional[str]:
    link = os.path.join(root, "latest")
    if os.path.islink(link) or os.path.isdir(link):
        return os.path.realpath(link)
    return None
