"""Multi-device scaling curve on the virtual CPU mesh.

Measures the four sharded checker paths at 1/2/4/8 devices
(`--devices` to override), one subprocess per device count (the XLA
device count is fixed at backend init):

- **keyed**  — `check_many` with the key axis sharded over the mesh
  (the `independent` hot path; data-parallel axis);
- **chunked** — `check_chunked` boolean transfer matrices with the
  chunk axis sharded via `shard_map` (history/sequence-parallel axis);
- **frontier** — the sparse engine with config rows hash-routed to
  owner shards via `all_to_all`;
- **lockstep** — `check_batch(devices=...)` through the mesh-lockstep
  lane (lockstep lane blocks placed per device, dispatch groups
  multi-queued). The CPU sweep has no Pallas hardware, so the lockstep
  gates are forced open with the kernel in interpret mode — the row
  measures the multi-queue scheduler and verdict fidelity under
  sharding, not kernel speed.

IMPORTANT caveat, printed with the results: on a host with fewer
physical cores than virtual devices the curve measures *sharding
overhead*, not parallel speedup — XLA's virtual CPU devices share the
host's cores. A flat curve on a 1-core host is the success criterion
there (the sharded program does ~1x total work); real speedup needs
real chips (or >= n_devices cores). `__graft_entry__.dryrun_multichip`
asserts a conservative >= 2x keyed speedup floor at 8 devices when the
host has the cores to show it (the timed region includes serial host
prep and per-iteration liveness all-reduces).

Usage: python tools/scaling.py [--devices 1,2,4,8] [--keys 512]
       [--chunk-ops 100000] [--quick]
Emits one JSON line per (path, n_devices) plus a final summary line
collecting best_s per path across the device counts.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)
sys.path.insert(0, _REPO)


def _worker(n_dev: int, keys: int, key_ops: int, chunk_ops: int,
            n_chunks: int, lockstep_keys: int,
            lockstep_ops: int) -> int:
    """Runs inside the subprocess: measure all four paths on an
    ``n_dev``-device mesh and print one JSON line per path."""
    import jax

    from jepsen_tpu import fixtures, models
    from jepsen_tpu.checkers import frontier, reach
    from jepsen_tpu.history import pack

    devs = jax.devices()[:n_dev]
    model = models.cas_register()

    def best_of(fn, n=2):
        fn()                                     # warm / compile
        best = float("inf")
        for _ in range(n):
            t0 = time.monotonic()
            fn()
            best = min(best, time.monotonic() - t0)
        return best

    # keyed: N keys sharded over the mesh
    packs = [pack(fixtures.gen_history("cas", n_ops=key_ops, processes=3,
                                       seed=s))
             for s in range(keys)]
    dt = best_of(lambda: reach.check_many(model, packs, devices=devs))
    print(json.dumps({"path": "keyed", "n_devices": n_dev, "keys": keys,
                      "key_ops": key_ops, "best_s": round(dt, 3)}),
          flush=True)

    # chunked: one long history, chunk axis sharded
    hist = fixtures.gen_history("cas", n_ops=chunk_ops, processes=5,
                                seed=42)
    packed = pack(hist)
    dt = best_of(lambda: reach.check_chunked(
        model, packed=packed, n_chunks=n_chunks, devices=devs,
        max_matrix=1 << 28))
    print(json.dumps({"path": "chunked", "n_devices": n_dev,
                      "ops": chunk_ops, "n_chunks": n_chunks,
                      "best_s": round(dt, 3)}), flush=True)

    # frontier: crash-seasoned register history, rows hash-routed.
    # crash parameters are deliberately light: every crashed op stays
    # forever-pending, and distinct-value crashed writes multiply the
    # quotiented config space (2 values / 1% keeps the set ~8k rows)
    hist = fixtures.gen_history("register", n_ops=1200, processes=4,
                                crash_p=0.01, values=2, seed=11)
    dt = best_of(lambda: frontier.check(models.register(), hist,
                                        frontier0=512, devices=devs))
    print(json.dumps({"path": "frontier", "n_devices": n_dev,
                      "ops": 1200, "best_s": round(dt, 3)}), flush=True)

    # lockstep: H complete histories through check_batch(devices=...) →
    # the mesh-lockstep lane. No Pallas hardware on the CPU sweep, so
    # the gates are forced open with the kernel in interpret mode
    # (LAST path in this worker — the patched gates must not leak into
    # the measurements above); an injected violation proves verdict
    # fidelity under sharding on every rung, and the ENGINE is asserted
    # so a silent decline to the keyed mesh-union walk can never be
    # reported as lockstep scaling data.
    from jepsen_tpu.checkers import preproc_native, reach_batch
    if not preproc_native.available():
        print(json.dumps({"path": "lockstep", "n_devices": n_dev,
                          "skipped": "native preprocessing library "
                                     "unavailable"}), flush=True)
        return 0
    reach._use_pallas = lambda: True
    reach._PALLAS_MIN_RETURNS = 0
    reach_batch._INTERPRET_DEFAULT = True
    for k in ("JEPSEN_TPU_NO_MESH_LOCKSTEP",
              "JEPSEN_TPU_NO_PACKED_XFER", "JEPSEN_TPU_NO_LAZY_FETCH",
              "JEPSEN_TPU_NO_DONATE"):
        os.environ.pop(k, None)   # the rung measures the mesh lane on
    #                               the full transfer diet (ISSUE 5)
    from jepsen_tpu.checkers import transfer
    # covers all three gates, and catches an env-var rename drifting
    # from the pop list above (which would silently re-close a gate)
    assert (transfer.packed_enabled() and transfer.lazy_fetch_enabled()
            and transfer.donate_enabled()), "diet gates must be open"
    packs_l = []
    for s in range(lockstep_keys):
        h = fixtures.gen_history("cas", n_ops=lockstep_ops, processes=3,
                                 seed=300 + s)
        if s == 1:
            h = fixtures.corrupt(h, seed=s)
        packs_l.append(pack(h))
    want = "reach-lockstep-mesh" if n_dev > 1 else "reach-lockstep"

    def _lockstep():
        res = reach.check_batch(model, packs_l, devices=devs)
        assert all(r["engine"] == want for r in res), \
            sorted({r["engine"] for r in res})
        assert res[1]["valid"] is False and all(
            r["valid"] is True for i, r in enumerate(res) if i != 1), \
            "lockstep verdicts drifted under sharding"
        # the lazy-fetch rescue (ISSUE 5): with verdicts fetched as
        # per-lane summaries, the full arrays cross the wire only when
        # a lane dies and witness reconstruction needs them — assert
        # per rung that the injected violation still surfaces its
        # knossos-style witness, so the rescue path is covered at
        # every mesh width
        assert res[1].get("final-configs"), \
            "lazy-fetch rescue lost the violation witness"
        assert res[1].get("op") is not None, \
            "lazy-fetch rescue lost the failing op"
        return res

    dt = best_of(_lockstep)
    print(json.dumps({"path": "lockstep", "n_devices": n_dev,
                      "engine": want, "keys": lockstep_keys,
                      "key_ops": lockstep_ops,
                      "best_s": round(dt, 3)}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--keys", type=int, default=512)
    ap.add_argument("--key-ops", type=int, default=100)
    ap.add_argument("--chunk-ops", type=int, default=100_000)
    ap.add_argument("--n-chunks", type=int, default=64)
    ap.add_argument("--lockstep-keys", type=int, default=16)
    ap.add_argument("--lockstep-ops", type=int, default=600)
    ap.add_argument("--quick", action="store_true",
                    help="small sizes for CI")
    ap.add_argument("--_worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.quick:
        args.keys, args.chunk_ops, args.n_chunks = 64, 10_000, 16
        args.lockstep_keys, args.lockstep_ops = 8, 240

    if args._worker is not None:
        return _worker(args._worker, args.keys, args.key_ops,
                       args.chunk_ops, args.n_chunks,
                       args.lockstep_keys, args.lockstep_ops)

    counts = [int(x) for x in args.devices.split(",")]
    cores = os.cpu_count() or 1
    print(json.dumps({"host_cores": cores, "note":
                      "with host_cores < n_devices the curve measures "
                      "sharding overhead, not speedup"}), flush=True)
    rows = []
    for n in counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, os.path.abspath(__file__),
               "--_worker", str(n),
               "--keys", str(args.keys), "--key-ops", str(args.key_ops),
               "--chunk-ops", str(args.chunk_ops),
               "--n-chunks", str(args.n_chunks),
               "--lockstep-keys", str(args.lockstep_keys),
               "--lockstep-ops", str(args.lockstep_ops)]
        # stdout is relayed line-by-line (the multi-minute sweep stays
        # live) while the rows are collected for the summary; stderr
        # passes through untouched so worker warnings are never lost
        p = subprocess.Popen(cmd, env=env, cwd=_REPO,
                             stdout=subprocess.PIPE, text=True)
        assert p.stdout is not None
        for line in p.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "path" in d and "best_s" in d:
                rows.append(d)
        rc = p.wait()
        if rc != 0:
            print(json.dumps({"n_devices": n, "error": rc}),
                  flush=True)
    # summary: best_s per path across the device sweep (the
    # flat-curve-on-few-cores caveat from the header line applies)
    summary: dict = {}
    for d in rows:
        summary.setdefault(d["path"], {})[str(d["n_devices"])] = \
            d["best_s"]
    print(json.dumps({"summary": summary, "host_cores": cores}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
