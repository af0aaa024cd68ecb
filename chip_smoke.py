"""Chip smoke: drive the checker's main path once on the TPU, through the
entry points a user calls, at the sizes users run, and check every
verdict against a reference.

    python chip_smoke.py             # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4   # the mesh-lockstep keyed batch on
                                     # four chips vs the same batch on one

Phases (one process; every phase on the default TPU device):

  (a) device   - fail unless ``jax.devices()[0].platform == "tpu"``
  (b) cas-100k - a 100k-op CAS-register history through the facade
                 (``linearizable``, algorithm "auto") and
                 ``reach.check_packed``: valid on a device engine, then
                 corrupted: invalid at the same op as ``wgl_native``
  (c) recheck  - the upstream-format EDN fixtures through the CLI entry
  (d) keyed    - ``reach.check_many`` over 4096 keys x 100 ops on the
                 lockstep lane, sampled keys against ``wgl_native``
  (e) serve    - an in-process ``serve.Daemon``: one-shot checks and a
                 streaming session over HTTP, verdicts against
                 ``wgl_native``

After every phase no fallback/swallow counter of ``obs`` may be
non-zero. Each phase prints its wall and compile seconds on its own
line; the last line of stdout is one JSON object
(``{"ok": true, "device": {...}}``) and is printed only on success.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import urllib.request

SEED = 20261015
DEVICE_ENGINES = ("reach-chunklock", "reach-pallas", "reach-word")
KEYED_ENGINE, MESH_ENGINE = "reach-lockstep", "reach-lockstep-mesh"
N_KEYS, KEY_OPS = 4096, 100
# counters that say a device path was abandoned or a crash was hidden
_BAD_PREFIXES = ("engine.fallback.", "checker.swallowed.")
_BAD_NAMES = ("reach.pallas_fallback", "serve.session.fallback",
              "serve.breaker.degraded_dispatches")


class SmokeFailure(AssertionError):
    pass


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Counts JAX's backend compiles and sums their durations
    (``jax.monitoring``)."""

    def __init__(self) -> None:
        import jax
        self.total, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.total += duration
            self.count += 1


def bad_counters() -> dict:
    from jepsen_tpu import obs
    counters = obs.snapshot()["counters"]
    return {k: v for k, v in counters.items()
            if v and (k.startswith(_BAD_PREFIXES) or k in _BAD_NAMES)}


def run_phase(name: str, fn, clock: CompileClock, report: dict):
    t0, c0, n0 = time.monotonic(), clock.total, clock.count
    out = fn()
    wall, comp = time.monotonic() - t0, clock.total - c0
    bad = bad_counters()
    line = {"phase": name, "wall_s": wall, "compile_s": comp,
            "compiles": clock.count - n0}
    line.update(out or {})
    print(json.dumps(line, default=str), flush=True)
    report[name] = line
    require(not bad, f"phase {name}: fallback counters {bad}")


# -- helpers -----------------------------------------------------------

def history_of(packed):
    """Op list (invoke + ok per entry, in event order) of a packed
    history, for the entry points that take a raw history."""
    from jepsen_tpu.op import Op

    events = []
    for i in range(packed.n):
        e = packed.entries[i]
        events.append((int(packed.inv_ev[i]), "invoke", e.op))
        events.append((int(packed.ret_ev[i]), "ok", e.op))
    events.sort(key=lambda t: t[0])
    return [Op(process=op.process, type=typ, f=op.f, value=op.value,
               index=k)
            for k, (_, typ, op) in enumerate(events)]


def reference(model, history):
    """``wgl_native``, or ``wgl_ref`` where the native search is not
    built."""
    from jepsen_tpu.checkers import wgl_native, wgl_ref
    if wgl_native.available():
        return wgl_native.check(model, history)
    return wgl_ref.check(model, history)


def same_op(a, b) -> bool:
    def norm(op):
        if op is None:
            return None
        d = op if isinstance(op, dict) else op.to_dict()
        return (d.get("process"), d.get("f"), json.dumps(d.get("value")),
                d.get("index"))
    return norm(a) == norm(b)


# -- phases ------------------------------------------------------------

def phase_device(expect_chips: int) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    require(d0.platform == "tpu", f"default device is {d0.platform!r}, "
                                  "not a TPU")
    require(len(devs) >= expect_chips,
            f"{len(devs)} devices, {expect_chips} needed")
    from jepsen_tpu.checkers import preproc_native, wgl_native
    require(preproc_native.available(),
            f"native preproc library did not load: "
            f"{preproc_native.build_error()}")
    require(wgl_native.available(),
            f"native wgl library did not load: {wgl_native.build_error()}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def phase_cas(n_ops: int = 100_000) -> dict:
    from jepsen_tpu import fixtures, models
    from jepsen_tpu import history as h
    from jepsen_tpu.checkers import facade, reach

    model = models.cas_register()
    packed = fixtures.gen_packed("cas", n_ops=n_ops, processes=5,
                                 seed=SEED)
    hist = history_of(packed)
    out: dict = {"ops": n_ops}
    res = facade.linearizable(model).check({"model": model}, hist)
    require(res.get("valid") is True, f"facade verdict {res.get('valid')}")
    require(res.get("engine") in DEVICE_ENGINES,
            f"facade engine {res.get('engine')}")
    out["facade_engine"] = res["engine"]
    res = reach.check_packed(model, packed)
    require(res.get("valid") is True, f"reach verdict {res.get('valid')}")
    require(res.get("engine") in DEVICE_ENGINES,
            f"reach engine {res.get('engine')}")
    out["reach_engine"] = res["engine"]

    bad = fixtures.corrupt(hist, seed=SEED)
    ref = reference(model, bad)
    require(ref.get("valid") is False, f"reference verdict {ref}")
    res = facade.linearizable(model).check({"model": model}, bad)
    require(res.get("valid") is False,
            f"corrupt facade verdict {res.get('valid')}")
    require(res.get("engine") in DEVICE_ENGINES,
            f"corrupt facade engine {res.get('engine')}")
    require(same_op(res.get("op"), ref.get("op")),
            f"failing op {res.get('op')} != reference {ref.get('op')}")
    res2 = reach.check_packed(model, h.pack(bad))
    require(res2.get("valid") is False and
            same_op(res2.get("op"), ref.get("op")),
            f"corrupt reach verdict {res2.get('valid')} op {res2.get('op')}")
    out["corrupt_engine"] = res["engine"]
    out["failing_op_index"] = (ref.get("op") or {}).get("index")
    return out


def phase_recheck(root: str) -> dict:
    from jepsen_tpu import cli

    cases = {"cas-register-bad.edn": 1, "cas-register-ok-large.edn": 0}
    out = {}
    for name, want_rc in cases.items():
        path = os.path.join(root, "data", name)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli.main(["recheck", path, "--model", "cas-register"])
        res = json.loads(buf.getvalue())
        want = want_rc == 0
        require(rc == want_rc and res.get("valid") is want,
                f"recheck {name}: rc {rc} valid {res.get('valid')}")
        out[name] = res.get("engine")
    return out


def keyed_batch(n_keys: int = N_KEYS, ops: int = KEY_OPS):
    """n_keys independent CAS histories; every 8th key corrupted."""
    from jepsen_tpu import fixtures
    from jepsen_tpu import history as h

    packed, hists = [], {}
    for k in range(n_keys):
        if k % 8 == 3:
            hist = fixtures.corrupt(
                fixtures.gen_history("cas", n_ops=ops, processes=5,
                                     seed=SEED + k), seed=k)
            hists[k] = hist
            packed.append(h.pack(hist))
        else:
            packed.append(fixtures.gen_packed("cas", n_ops=ops,
                                              processes=5, seed=SEED + k))
    return packed, hists


def phase_keyed(batch) -> dict:
    from jepsen_tpu import models
    from jepsen_tpu.checkers import reach, wgl_native

    model = models.cas_register()
    packed, hists = batch
    res = reach.check_many(model, packed)
    engines = sorted({r.get("engine") for r in res})
    require(engines == [KEYED_ENGINE], f"keyed engines {engines}")
    n_bad = sum(r.get("valid") is False for r in res)
    sample = list(range(0, len(packed), max(1, len(packed) // 64)))
    sample += sorted(hists)[:64]
    for k in sample:
        ref = wgl_native.check_packed(model, packed[k])
        require(res[k].get("valid") == ref.get("valid"),
                f"key {k}: {res[k].get('valid')} != {ref.get('valid')}")
    return {"keys": len(packed), "engines": engines, "invalid": n_bad,
            "sampled": len(sample)}


def _http(url: str, method: str, path: str, body=None, timeout=120.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url + path, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def phase_serve() -> dict:
    from jepsen_tpu import fixtures, models, obs, serve

    model = models.cas_register()
    daemon = serve.Daemon(port=0, host="127.0.0.1").start()
    url = f"http://127.0.0.1:{daemon.port}"
    out: dict = {}
    try:
        jobs = []
        for i, n in enumerate((200, 400, 800, 1600, 200, 400, 800, 1600)):
            hist = fixtures.gen_history("cas", n_ops=n, processes=5,
                                        seed=SEED + 100 + i)
            if i % 2:
                hist = fixtures.corrupt(hist, seed=i)
            code, r = _http(url, "POST", "/check",
                            {"model": "cas-register", "tenant": f"t{i % 3}",
                             "history": [op.to_dict() for op in hist]})
            require(code == 202, f"POST /check -> {code} {r}")
            jobs.append((r["id"], hist))
        engines = set()
        deadline = time.monotonic() + 600
        for rid, hist in jobs:
            while True:
                code, r = _http(url, "GET", f"/check/{rid}")
                if r.get("status") in ("done", "failed", "expired",
                                       "cancelled", "quarantined"):
                    break
                require(time.monotonic() < deadline, f"{rid} never finished")
                time.sleep(0.05)
            res = r.get("result") or {}
            ref = reference(model, hist)
            require(res.get("valid") == ref.get("valid"),
                    f"serve {rid}: {r.get('status')} {res.get('valid')} "
                    f"!= {ref.get('valid')}")
            engines.add(res.get("engine"))
        out["check_engines"] = sorted(str(e) for e in engines)

        hist = fixtures.corrupt(fixtures.gen_history(
            "cas", n_ops=600, processes=5, seed=SEED + 7), seed=7)
        code, r = _http(url, "POST", "/session",
                        {"model": "cas-register", "tenant": "sess"})
        require(code == 201, f"POST /session -> {code} {r}")
        sid = r["session"]
        blocks = [hist[i:i + 200] for i in range(0, len(hist), 200)]
        for seq, b in enumerate(blocks, start=1):
            code, r = _http(url, "POST", f"/session/{sid}/append",
                            {"history": [op.to_dict() for op in b],
                             "seq": seq})
            require(code == 200, f"append {seq} -> {code} {r}")
        code, r = _http(url, "POST", f"/session/{sid}/close", {})
        require(code == 200, f"close -> {code} {r}")
        res = r["result"]
        ref = reference(model, hist)
        require(res.get("valid") == ref.get("valid") and
                same_op(res.get("op"), ref.get("op")),
                f"session verdict {res.get('valid')} op {res.get('op')} "
                f"!= {ref.get('valid')} {ref.get('op')}")
        out["session_engine"] = res.get("engine")
        out["session_appends"] = len(blocks)
    finally:
        daemon.shutdown(drain_timeout=30.0)
    device_s = obs.snapshot()["counters"].get("serve.device_s", 0.0)
    require(device_s > 0, f"serve.device_s = {device_s}")
    out["serve_device_s"] = device_s
    return out


def phase_mesh(batch, n_chips: int) -> dict:
    import jax

    from jepsen_tpu import models
    from jepsen_tpu.checkers import reach

    model = models.cas_register()
    packed, _ = batch
    devs = jax.devices()[:n_chips]
    diag: dict = {}
    res = reach.check_many(model, packed, devices=devs, diag=diag)
    one = reach.check_many(model, packed, devices=devs[:1])
    engines = sorted({r.get("engine") for r in res})
    require(engines == [MESH_ENGINE], f"mesh engines {engines}")
    require(sorted({r.get("engine") for r in one}) == [KEYED_ENGINE],
            "one-chip engines")
    diffs = [k for k, (a, b) in enumerate(zip(res, one))
             if a.get("valid") != b.get("valid")]
    require(not diffs, f"mesh vs one chip differ on keys {diffs[:10]}")
    per_dev = (diag.get("mesh") or {}).get("per_device_groups")
    require(per_dev is not None and len(per_dev) == n_chips
            and all(c > 0 for c in per_dev),
            f"per_device_groups {per_dev}")
    return {"keys": len(packed), "engines": engines,
            "per_device_groups": per_dev,
            "invalid": sum(r.get("valid") is False for r in res)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh-lockstep keyed batch on "
                         "four chips against one")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.abspath(__file__))
    try:
        import jax
        clock = CompileClock()
        report: dict = {}
        run_phase("device", lambda: phase_device(args.chips), clock,
                  report)
        dev = report["device"]
        if args.chips == 4:
            batch = keyed_batch()
            run_phase("mesh", lambda: phase_mesh(batch, 4), clock, report)
        else:
            run_phase("cas", phase_cas, clock, report)
            run_phase("recheck", lambda: phase_recheck(root), clock,
                      report)
            batch = keyed_batch()
            run_phase("keyed", lambda: phase_keyed(batch), clock, report)
            run_phase("serve", phase_serve, clock, report)
        count = len(jax.devices())
    except Exception as e:                              # noqa: BLE001
        print(f"chip smoke FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
