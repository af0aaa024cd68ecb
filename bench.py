"""Benchmark entry point — run the BASELINE.md ladder's headline config and
print ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Headline (BASELINE.json north star): verify a 100k-op CAS-register history
for linearizability in <60 s on TPU; metric is ops verified per second, and
``vs_baseline`` is measured throughput over the north-star floor
(100_000 ops / 60 s ≈ 1667 ops/s). The reference publishes no numbers of its
own (SURVEY.md §6) — CPU Knossos folklore is that 100k-op single-key
histories simply time out.

With ``--engine reach`` (the default) the run also reports a
kernel-level probe (SURVEY.md §5 tracing): steady-state device time of
the lane kernel separated from host->device transfer and the
dispatch/fetch round-trip, plus an honest MFU figure. The probe drives
the PRODUCTION dispatch path (``reach_lane._pipe_walk`` — the same
segmented programs ``check_packed`` runs) and times the kernel by
dispatch slope (K queued walks + one fetch, minus a single walk +
fetch); how the slope compares with a ``block_until_ready`` timing is
unmeasured on the chip. The bare round-trip latency is sampled separately
(min of several dispatch+fetch cycles of a jitted scalar reduction
over the already-resident operand set — the same observer the
transfer measurement pays) and subtracted from the transfer figure,
so ``transfer_sync_s`` is bytes on the wire, not latency; raw
put+observe = ``transfer_sync_s + rtt_s``.

The default run's ``"batch"`` sub-object carries the lockstep batch
rung (``reach.check_batch``) with its bucketed-dispatch diagnostics:
per-bucket geometry (``per_bucket``: H/B/W/S/R_pad and real vs padded
returns per lockstep group), ``pack_efficiency`` (real returns over
padded lockstep steps — the win of length-bucketed lane packing),
``kernel_cache`` (hit/miss counters of the per-geometry compiled-kernel
cache), the mesh scaling story (``n_devices``, ``per_device_groups``,
``mesh_pad_lanes`` — 1/None/0 on single-device runs), and aggregate
ops/s. ``--engine batch`` promotes the batch
dimension to the HEADLINE: a ragged independent-keys workload
(BASELINE config #4 shape — ``--ops`` total over ≥8 keys of mixed
lengths) through ``reach.check_many``'s bucketed lockstep lane,
reported against the sequential per-key baseline measured in the same
run. All of it lands in the BENCH_*.json trajectory artifacts.

Every run also emits an ``"obs"`` sub-object — the
:mod:`jepsen_tpu.obs` snapshot taken over the run: the engine-decision
ledger (which engine the measured check selected, every fallback with
its cause), the cache/fallback counters (``reach.pallas_fallback``,
``lockstep.kernel_cache.*``, ``lockstep.transfer_bytes``, pack
efficiency), and the span count — and writes a Chrome/Perfetto
``trace.json`` (``--trace PATH``, empty string disables) that
``tools/trace_view.py`` summarizes.

``--serve`` appends a ``"serve"`` sub-object: an in-process
checker-as-a-service daemon (ISSUE 6) driven by the open-loop load
generator (``tools/loadgen.py``) — sustained req/s, p50/p99 verdict
latency across two measurement windows (the second runs entirely on
warm caches), backpressure/timeout counts, and the daemon's final
``serve.*`` counter snapshot — plus a ``"session"`` sub-object (ISSUE
11): the streaming-session rung, sustained append ops/s and p50/p99
append-to-verdict latency of the device-resident carried-frontier
engine vs the host ``OnlineLinearizable`` monitor at its production
flush cadence, with the jax ``platform`` named so the device-vs-host
comparison reads honestly on CPU-only runs — and a ``"session_mux"``
sub-object (ISSUE 16): L live same-geometry streams advanced through
ONE vmapped mega-batch launch per wave vs L per-session launches, at
several lane widths up to 5000 sessions, appends/s and p99 both ways
with the measured crossover persisted to the autotune table.

Usage: python bench.py [--ops N] [--repeat K]
       [--engine reach|chunked|batch|wgl-cpu|wgl-native]
       [--trace trace.json] [--serve]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


# published peaks per chip, keyed by jax's ``device_kind``, for the MFU
# denominator (the walk is latency-bound tiny-matmul work, so MFU is
# honestly tiny — the point of reporting it). Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


def _peak(key: str) -> float:
    """Published peak ``key`` of the default device; a device kind not
    in :data:`_PEAKS` is an error, never a default."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in _PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return _PEAKS[kind][key]


def _device_info() -> dict:
    """The device every number of this run was measured on."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _probe_errors(out: dict, path: str = "") -> list:
    """Dotted paths of every probe sub-object that carries ``error``."""
    errs = [path.rstrip(".")] if path and "error" in out else []
    for k, v in out.items():
        if isinstance(v, dict) and k != "obs":
            errs += _probe_errors(v, f"{path}{k}.")
    return errs


def _lane_operands(model, packed):
    """The single-history lane operand set every probe measures: memo
    BFS + union transition tensor + the PRODUCTION packing
    (``reach_lane.pack_operands``). Shared so one bench run pays this
    host prep once for both ``transfer_probe`` and ``kernel_probe``.
    Returns ``(rs, geom, host_args, p_nbytes)``."""
    import numpy as np

    from jepsen_tpu.checkers import events as ev
    from jepsen_tpu.checkers import reach, reach_lane

    memo, stream, _T, S, M = reach._prep(
        model, packed, max_states=100_000, max_slots=20,
        max_dense=1 << 22)
    rs = ev.returns_view(stream)
    P_np = reach._build_P(memo, S)
    R0 = np.zeros((S, M), bool)
    R0[0, 0] = True
    geom, _, _, host_args = reach_lane.pack_operands(
        P_np, rs.ret_slot, rs.slot_ops, R0)
    return rs, geom, host_args, int(P_np.nbytes)


def _pallas_needs_accelerator() -> bool:
    """True when compiled-Pallas probes cannot run on this backend
    (CPU only supports interpret mode, whose timings would mislead)."""
    try:
        import jax
        return jax.default_backend() == "cpu"
    except Exception:                                   # noqa: BLE001
        return True


def kernel_probe(model, packed, prep=None, prep_s=None) -> dict:
    """Steady-state device-kernel probe for the single-history lane
    walk: returns kernel_s (dispatch-slope), transfer_s / bytes, the
    dispatch+fetch round-trip, and MFU. Raises if the lane path does
    not admit the history (caller treats the probe as best-effort).
    ``prep``/``prep_s`` carry a pre-built :func:`_lane_operands` set
    (and its measured wall) so a full bench run preps once."""
    import numpy as np

    import jax
    from jepsen_tpu.checkers import reach_lane

    if prep is None:
        t_prep = time.monotonic()
        prep = _lane_operands(model, packed)
        prep_s = time.monotonic() - t_prep
    # marshaling AND dispatch shared with the production path — the
    # probe runs reach_lane._pipe_walk itself, so it can never time a
    # kernel or a pipeline production does not execute
    rs, geom, host_args, p_nbytes = prep
    R_real = int(rs.ret_slot.shape[0])
    B, W, M, S, O1, R_pad = geom
    n_pass = min(W, reach_lane._FAST_PASSES)
    from jepsen_tpu.checkers import transfer as xfer

    # the put-observer moves the TRUE production wire: the dominant
    # slot_ops lane crosses 6-bit packed PER SEGMENT (exactly what
    # _pipe_walk uploads, ragged-tail pad included), so transfer_sync_s
    # and the reported bytes describe the same transfer — the diet, not
    # the pre-pack host staging arrays
    _rs_w, _so_w, _P_w, _r0_w = host_args
    if xfer.packed_enabled() and xfer.sextet_ok(O1):
        wire_args = (_rs_w, reach_lane.pack_ops_wire(geom, _so_w),
                     _P_w, _r0_w)
    else:
        wire_args = host_args
    n_bytes = reach_lane.wire_bytes(geom, host_args)

    # the probe's verdict fetch matches the production protocol: lazy
    # (the default) crosses ONE on-device-reduced boolean, eager the
    # full [M, S] final set — so dispatch_fetch_s reflects the diet
    if xfer.lazy_fetch_enabled():
        def verdict_fetch(fin):
            return bool(np.asarray(reach_lane._jit_any()(fin)))
    else:
        def verdict_fetch(fin):
            return np.asarray(fin)
    dsegs: dict = {}
    _, final = reach_lane._pipe_walk(host_args, geom, n_pass, False,
                                     dsegs)
    _ = verdict_fetch(final)                    # warm/compile
    # put-completion observer: a scalar reduction CONSUMING every
    # operand, jitted once. Fetching a put array back is free (jax
    # keeps the committed host copy), so observing transfer completion
    # requires a device computation that depends on the bytes.
    import jax.numpy as jnp
    observe = jax.jit(lambda a, b, c, d: (
        a.astype(jnp.int32).sum() + b.astype(jnp.int32).sum()
        + c.sum().astype(jnp.int32) + d.sum().astype(jnp.int32)))
    args2 = jax.device_put(wire_args)
    _ = int(observe(*args2))                    # warm/compile
    # bare dispatch+fetch round trip on RESIDENT operands — the latency
    # floor every sync pays regardless of bytes moved (min of several
    # samples: single-shot jitter is the same order as the transfer)
    rtts = []
    for _i in range(4):
        t0 = time.monotonic()
        _ = int(observe(*args2))
        rtts.append(time.monotonic() - t0)
    rtt_s = min(rtts)
    # transfer: one put of the full operand set, forced to completion
    # by the observer; the observer's own dispatch+fetch is latency,
    # not transfer, so the sampled floor is subtracted. Raw
    # put+observe = transfer_sync_s + rtt_s.
    t0 = time.monotonic()
    args2 = jax.device_put(wire_args)
    _ = int(observe(*args2))
    transfer_s = max(0.0, time.monotonic() - t0 - rtt_s)
    put_s = transfer_s
    # steady-state walk split into its pipeline stages: dispatch_s is
    # the host time to queue every device program, fetch_s the
    # verdict round-trip — together with prep_s these attribute the
    # ~47 ms of check_s the kernel slope leaves unexplained, so the
    # overlap win is measurable run-over-run
    t0 = time.monotonic()
    _, final = reach_lane._pipe_walk(host_args, geom, n_pass, False,
                                     dsegs)
    t1 = time.monotonic()
    _ = verdict_fetch(final)
    t2 = time.monotonic()
    dispatch_only_s = t1 - t0
    fetch_s = t2 - t1
    one_s = t2 - t0                       # 1 walk (dispatches) + fetch
    K = 6
    t0 = time.monotonic()
    for _i in range(K):
        _, final = reach_lane._pipe_walk(host_args, geom, n_pass, False,
                                         dsegs)
    _ = verdict_fetch(final)
    many_s = time.monotonic() - t0
    kernel_s = max(0.0, (many_s - one_s) / (K - 1))
    # FLOPs: min(c_r, n_pass) fire matmuls [M,S]@[S,W*S] per return —
    # the gate ladder executes exactly the pending-count bound (the VPU
    # reshuffles and projection move bytes, not FLOPs)
    executed = np.minimum(
        (rs.slot_ops >= 0).sum(axis=1), n_pass).sum()
    flops = 2.0 * M * S * W * S * float(executed)
    # transfer-diet breakdown: actual bytes on the wire (narrow ints +
    # bit-packed bools) vs the blanket int32/f32 format, and which
    # fetch protocol the verdict crossed on — the run-over-run evidence
    # the CI transfer-guard budgets
    unpacked_bytes = reach_lane.blanket_bytes(geom, p_nbytes)
    return {
        "kernel_s": round(kernel_s, 4),
        "kernel_ns_per_return": round(kernel_s / max(R_real, 1) * 1e9),
        "returns": R_real,
        "transfer_sync_s": round(transfer_s, 4),
        "transfer_bytes": int(n_bytes),
        # put_s/packed_bytes alias the two fields above under the
        # round-6 names the transfer tooling reads; the round-5 names
        # stay so BENCH_r01-r05 comparisons keep working
        "put_s": round(put_s, 4),
        "packed_bytes": int(n_bytes),
        "unpacked_bytes": int(unpacked_bytes),
        "fetch_mode": xfer.fetch_mode(),
        "rtt_s": round(rtt_s, 4),
        "dispatch_fetch_s": round(one_s - kernel_s, 4),
        "prep_s": round(prep_s, 4),
        "dispatch_s": round(dispatch_only_s, 4),
        "fetch_s": round(fetch_s, 4),
        "mfu_pct": round(flops / max(kernel_s, 1e-9)
                         / _peak("bf16_flops") * 100, 4),
    }


def transfer_probe(model, packed, prep=None) -> dict:
    """Host-only marshalling breakdown of the single-history wire
    format: runs the PRODUCTION operand packing
    (``reach_lane.pack_operands`` — no device dispatch, so this works
    on CPU-only CI) and reports actual vs blanket-int32/f32 bytes.
    The ``transfer-guard`` CI step budgets these numbers so a wire
    regression (a re-widened dtype, an unpacked bool tensor) fails the
    build. ``prep`` reuses a :func:`_lane_operands` set."""
    from jepsen_tpu.checkers import reach_lane
    from jepsen_tpu.checkers import transfer as xfer

    if prep is None:
        prep = _lane_operands(model, packed)
    rs, geom, host_args, p_nbytes = prep
    # reach_lane.wire_bytes is the production accounting — it includes
    # the per-segment 6-bit packing of the dominant slot_ops lane that
    # _pipe_walk applies at upload time, so the guard budgets what the
    # link actually carries
    packed_bytes = int(reach_lane.wire_bytes(geom, host_args))
    unpacked_bytes = int(reach_lane.blanket_bytes(geom, p_nbytes))
    round5_bytes = int(reach_lane.round5_bytes(geom, p_nbytes))
    return {
        "returns": int(rs.n_returns),
        "packed_bytes": packed_bytes,
        "unpacked_bytes": unpacked_bytes,
        # ratio is vs the dtype-blind blanket reference the guard
        # budgets; vs_round5 is vs the narrow wire round 5 actually
        # shipped (upload side only — the fetch-side win is separate)
        "ratio": round(unpacked_bytes / max(packed_bytes, 1), 2),
        "round5_bytes": round5_bytes,
        "vs_round5": round(round5_bytes / max(packed_bytes, 1), 2),
        "bytes_per_return": round(
            packed_bytes / max(int(rs.n_returns), 1), 2),
        "fetch_mode": xfer.fetch_mode(),
        "gates": {"packed": xfer.packed_enabled(),
                  "lazy_fetch": xfer.lazy_fetch_enabled(),
                  "donate": xfer.donate_enabled()},
    }


def chunklock_probe(model, packed) -> dict:
    """Steady-state timing of the chunk-lockstep walk — the production
    single-history engine at the headline rung (round-5): warm best-of
    e2e of the full phase-A/glue/phase-B/fold dispatch chain, plus its
    geometry diagnostics."""
    import time as _t

    from jepsen_tpu.checkers import events as ev
    from jepsen_tpu.checkers import reach
    from jepsen_tpu.checkers import reach_chunklock as rcl

    memo, stream, _T, S, M = reach._prep(
        model, packed, max_states=100_000, max_slots=20,
        max_dense=1 << 22)
    rs = ev.returns_view(stream)
    if not rcl.admits(S, M, max(stream.W, 1), rs.n_returns):
        return {"skipped": "outside chunklock envelope"}
    P = reach._build_P(memo, S)
    dead, diag = rcl.walk_chunklock(P, rs.ret_slot, rs.slot_ops, M)
    times = []
    for _ in range(4):
        t0 = _t.monotonic()
        dead, diag = rcl.walk_chunklock(P, rs.ret_slot, rs.slot_ops, M)
        times.append(_t.monotonic() - t0)
    best = min(times)
    return {"walk_s": round(best, 4),
            "ns_per_return": round(best / max(rs.n_returns, 1) * 1e9),
            "returns": int(rs.n_returns), "dead": int(dead), **diag}


def batch_probe(model, n_ops: int, seed: int, processes: int) -> dict:
    """Lockstep batch rung (BASELINE.md round-4): H independent
    histories through ONE ``reach.check_batch`` call — the batch axis
    is where the device wins end-to-end, so the official bench
    artifact carries its aggregate throughput alongside the
    single-history headline. Warm best-of-2 e2e (includes union prep
    and marshaling — the honest user cost)."""
    from jepsen_tpu import fixtures
    from jepsen_tpu.checkers import reach

    H = reach._BATCH_GROUP
    packeds = [fixtures.gen_packed("cas", n_ops=n_ops,
                                   processes=processes,
                                   seed=seed + 1000 + i)
               for i in range(H)]
    diag: dict = {}
    res = reach.check_batch(model, packeds, diag=diag)  # warm/compile
    if not all(r["valid"] is True for r in res):
        return {"error": "bad batch verdicts"}
    engines = {r["engine"] for r in res}
    if engines != {"reach-lockstep"}:
        # the lockstep gates did not hold (CPU-only run, no native
        # lib, ...) and check_batch fell back to sequential
        # per-history checks — timing that as "the batch rung" would
        # mislabel sequential throughput, so skip like kernel_probe
        return {"skipped": f"no lockstep path ({sorted(engines)})"}
    times = []
    best_diag = diag
    for _ in range(2):
        d: dict = {}
        t1 = time.monotonic()
        reach.check_batch(model, packeds, diag=d)
        dt = time.monotonic() - t1
        if not times or dt < min(times):
            best_diag = d or diag
        times.append(dt)
    best = min(times)
    prep = best_diag.get("prep", {})
    mesh = best_diag.get("mesh") or {}
    return {"H": H, "e2e_s": round(best, 3),
            "agg_ops_s": round(H * n_ops / best),
            "engine": sorted(engines),
            # mesh scaling story (single-device runs report 1 device,
            # no per-device split): device count, groups walked per
            # device, and the lane-pad waste of sharding
            "n_devices": mesh.get("n_devices", 1),
            "per_device_groups": mesh.get("per_device_groups"),
            "mesh_pad_lanes": mesh.get("pad_lanes", 0),
            # prep/dispatch/fetch attribution of the best e2e run —
            # prep_hidden_s / prep_s is the streaming overlap win
            "prep_s": prep.get("wall_s"),
            "prep_hidden_s": prep.get("hidden_s"),
            "prep_mode": prep.get("mode"),
            "dispatch_s": best_diag.get("dispatch_s"),
            "fetch_s": best_diag.get("fetch_s"),
            # transfer-diet evidence: wire bytes under the diet vs the
            # blanket format, and the verdict fetch protocol
            "transfer": best_diag.get("transfer"),
            "pack_efficiency": best_diag.get("pack_efficiency"),
            "real_returns": best_diag.get("real_returns"),
            "padded_returns": best_diag.get("padded_returns"),
            "kernel_cache": best_diag.get("kernel_cache"),
            "per_bucket": best_diag.get("groups", [])}


def serve_probe(quick: bool = True) -> dict:
    """The serving-layer rung: self-host a daemon on an ephemeral
    port, replay a mixed-geometry multi-tenant workload at a target
    arrival rate through ``tools/loadgen.py``, and report sustained
    req/s + p50/p99 verdict latency (two windows: the second is the
    steady state a long-lived daemon lives in)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "loadgen.py")
    spec = importlib.util.spec_from_file_location("bench_loadgen",
                                                  path)
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)
    report = loadgen.run_loadgen({"quick": quick,
                                  "find_capacity": True})
    # the full per-request record set is loadgen's business; keep the
    # bench artifact to the headline numbers + the daemon's counters
    keep = ("warmup", "target_rate", "duration_s", "submitted",
            "completed", "rejected_429", "timeouts",
            "verdict_mismatches", "sustained_req_s", "saturated",
            "capacity", "p50_s",
            "p99_s", "p50_admit_s", "p99_admit_s", "windows",
            "stage_split", "latency_crosscheck",
            "fallbacks", "drained", "error")
    out = {k: report[k] for k in keep if k in report}
    stats = report.get("stats", {})
    out["counters"] = {k: v
                       for k, v in stats.get("counters", {}).items()
                       if k.startswith(("serve.", "pipeline."))}
    out["dispatch"] = stats.get("dispatch", {})
    # the daemon's histogram-derived tails + padding waste: the
    # serving-quality numbers BENCH_r*.json tracks across PRs
    out["histograms"] = stats.get("histograms", {})
    out["pad_waste_s"] = stats.get("counters", {}).get(
        "serve.pad_waste_s")
    out["device_s"] = stats.get("counters", {}).get("serve.device_s")
    # the fleet rung (ISSUE 15): two replica daemons over ONE shared
    # store root, loadgen round-robin across both, scaling efficiency
    # against the single-daemon sustained rate measured above
    try:
        out["fleet"] = _fleet_serve_probe(
            loadgen, baseline=out.get("sustained_req_s"), quick=quick)
    except Exception as e:                              # noqa: BLE001
        out["fleet"] = {"error": f"{type(e).__name__}: {e}"}
    return out


def _fleet_serve_probe(loadgen, *, baseline, quick=True) -> dict:
    """Spawn 2 ``check-serve`` replica subprocesses over one store
    root (reusing the chaos harness's process manager), drive
    loadgen's client-side round-robin at them, and report the merged
    throughput + scaling efficiency + per-replica lease counters
    (claims prove the shared-journal partition actually engaged).

    CPU backends only: a chip belongs to one process, and this process
    already holds it — replica subprocesses could not reach it (and the
    chaos harness pins them to the CPU), so on an accelerator the rung
    is a structured skip, never CPU replicas' numbers."""
    import importlib.util
    import os
    import shutil
    import tempfile

    import jax
    if jax.default_backend() != "cpu":
        return {"skipped": "one-process-per-chip: check-serve replica "
                           "subprocesses cannot reach the chip this "
                           "process holds"}

    cpath = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "chaos.py")
    spec = importlib.util.spec_from_file_location("bench_chaos", cpath)
    chaos = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chaos)
    root = tempfile.mkdtemp(prefix="bench-fleet-")
    procs = [chaos.DaemonProc(
        root, faults_env="",
        log_path=os.path.join(root, f"r{i}.log"),
        extra_args=["--replica-id", f"r{i}",
                    "--lease-ttl", "10.0", "--lanes", "2"])
        for i in range(2)]
    try:
        rep = loadgen.run_loadgen({
            "quick": quick,
            "replicas": [p.url for p in procs],
            "baseline_req_s": baseline})
        fleet = dict(rep.get("fleet") or {})
        for k in ("sustained_req_s", "p50_s", "p99_s", "submitted",
                  "completed", "verdict_mismatches", "error"):
            if rep.get(k) is not None:
                fleet[k] = rep[k]
        leases = {}
        for i, p in enumerate(procs):
            code, st = loadgen._get(p.url, "/stats")
            if code == 200:
                leases[f"r{i}"] = {
                    k: v for k, v in st.get("counters", {}).items()
                    if k.startswith("serve.lease.")}
        fleet["lease_counters"] = leases
        return fleet
    finally:
        for p in procs:
            try:
                p.sigterm()
            except Exception:                           # noqa: BLE001
                try:
                    p.sigkill()
                except Exception:                       # noqa: BLE001
                    pass
        shutil.rmtree(root, ignore_errors=True)


def session_probe(n_ops: int = 100_000, seed: int = 42,
                  block: int = 4096, quick: bool = False) -> dict:
    """The streaming-session rung (ISSUE 11): one cas op stream fed
    twice — once through the device-resident session engine
    (``serve.session.Session``: carried frontier advanced in place
    per append block, donated buffers) and once through the host
    ``OnlineLinearizable`` monitor at its production flush cadence —
    reporting sustained append ops/s and the p50/p99
    append-to-verdict latency for both. ``platform`` names the jax
    backend the session walk actually ran on: the device-resident
    path exists to beat the host monitor where there IS a device
    (the post-hoc walk does 8.9M ops/s there); on a CPU-only jax the
    same XLA program is thunk-overhead-bound and the C++ host monitor
    keeps the crown — the honest number either way."""
    import jax

    from jepsen_tpu import fixtures, models
    from jepsen_tpu.checkers import online
    from jepsen_tpu.serve.session import Session

    if quick:
        n_ops = min(n_ops, 20_000)
    hist = fixtures.gen_history("cas", n_ops=n_ops, processes=5,
                                seed=seed)
    model = models.cas_register()
    blocks = [hist[i:i + block] for i in range(0, len(hist), block)]

    def drive_session() -> dict:
        s = Session("bench", "bench", "cas-register", model)
        lats = []
        t0 = time.monotonic()
        verdict = True
        for i, b in enumerate(blocks):
            t1 = time.monotonic()
            r = s.advance_block(b, seq=i + 1)
            lats.append(time.monotonic() - t1)
            verdict = verdict and r["valid-so-far"]
        wall = time.monotonic() - t0
        lats.sort()
        return {"wall_s": round(wall, 3),
                "ops_s": round(len(hist) / wall),
                "engine": s.engine_name,
                "valid": verdict,
                "appends": len(blocks),
                "append_p50_s": round(lats[len(lats) // 2], 4),
                "append_p99_s": round(
                    lats[min(len(lats) - 1,
                             int(len(lats) * 0.99))], 4)}

    def drive_host() -> dict:
        mon = online.OnlineLinearizable(model)
        lats = []
        t0 = time.monotonic()
        n = 0
        for op in hist:
            mon.observe(op)
            n += 1
            if n % 256 == 0:        # the monitor's production cadence
                t1 = time.monotonic()
                mon.flush()
                lats.append(time.monotonic() - t1)
        res = mon.stop()
        wall = time.monotonic() - t0
        lats.sort()
        return {"wall_s": round(wall, 3),
                "ops_s": round(len(hist) / wall),
                "engine": ("online-native"
                           if type(mon._engine).__name__
                           == "NativeStreamEngine" else "online-py"),
                "valid": res.get("valid"),
                "flush_p50_s": (round(lats[len(lats) // 2], 5)
                                if lats else None),
                "flush_p99_s": (round(
                    lats[min(len(lats) - 1,
                             int(len(lats) * 0.99))], 5)
                    if lats else None)}

    sess_cold = drive_session()     # compile wall included
    sess_warm = drive_session()     # the steady state a daemon lives in
    host = drive_host()
    out = {
        "ops": len(hist), "block": block,
        "platform": jax.default_backend(),
        "session": sess_warm,
        "session_cold": sess_cold,
        "host_monitor": host,
        "session_vs_host": round(
            sess_warm["ops_s"] / max(host["ops_s"], 1), 3),
        "beats_host": sess_warm["ops_s"] > host["ops_s"],
    }
    if sess_warm["valid"] is not True or host["valid"] is not True:
        out["error"] = (f"verdict drift: session "
                        f"{sess_warm['valid']} host {host['valid']}")
    return out


def session_mux_probe(widths=(8, 64, 512, 5000), waves: int = 6,
                      quick: bool = False) -> dict:
    """The session-multiplexing rung (ISSUE 16): L live streams of
    identical walk geometry advanced one wave at a time, first
    member-by-member (L launches per wave — the pre-mux daemon) and
    then through ``session.advance_group`` (ONE vmapped launch per
    wave), at several lane widths. Streams use a closed two-value
    alphabet so the geometry never regrows and every lane stays in
    the group — the pure dispatch-amortization number, no coalescer
    noise. Reports appends/s and p99 append-to-verdict both ways per
    width (a batched append's latency is its wave's wall — the
    append is not done until its launch lands), and persists the
    measured crossover (the smallest width where the batch wins) in
    the autotune table for ``session.mega_crossover``."""
    import jax

    from jepsen_tpu import models
    from jepsen_tpu.checkers import autotune
    from jepsen_tpu.op import invoke, ok
    from jepsen_tpu.serve import session as sessmod
    from jepsen_tpu.serve.session import Session

    if quick:
        widths = tuple(w for w in widths if w <= 64) or (8, 64)
        waves = 3
    b1 = [invoke(0, "write", 1), ok(0, "write", 1),
          invoke(1, "read"), ok(1, "read", 1),
          invoke(0, "write", 2), ok(0, "write", 2),
          invoke(1, "read"), ok(1, "read", 2)]
    bw = [invoke(1, "write", 1), ok(1, "write", 1),
          invoke(0, "read"), ok(0, "read", 1),
          invoke(0, "write", 2), ok(0, "write", 2),
          invoke(1, "read"), ok(1, "read", 2)]
    model = models.register()

    def seed_sessions(prefix: str, n: int):
        ss = [Session(f"{prefix}{i}", f"t{i % 8}", "register", model)
              for i in range(n)]
        for s in ss:                    # solo seed: signatures align
            s.advance_block(b1, seq=1)
        return ss

    def drive(n: int, grouped: bool) -> dict:
        ss = seed_sessions("mega" if grouped else "solo", n)
        lats = []
        t0 = time.monotonic()
        valid = True
        for w in range(waves):
            entries = [(s, list(bw), w + 2) for s in ss]
            # every lane's append "arrives" at the wave's cadence
            # tick, so an append's latency runs from wave start to
            # ITS verdict: the batched members all land with the
            # launch; the per-session members queue behind their
            # predecessors on the one dispatcher — the real shape
            # mux replaces
            t1 = time.monotonic()
            if grouped:
                # force: a previously persisted session-mega
                # crossover must not silently re-route small widths
                # to the per-session path mid-measurement
                rs = sessmod.advance_group(entries, force=True)
                lats.extend([time.monotonic() - t1] * n)
            else:
                for s, b, q in entries:
                    r = s.advance_block(b, seq=q)
                    lats.append(time.monotonic() - t1)
                    valid = valid and r["valid-so-far"]
                rs = []
            valid = valid and all(r["valid-so-far"] for r in rs)
        wall = time.monotonic() - t0
        lats.sort()
        return {"wall_s": round(wall, 3),
                "appends_s": round(n * waves / wall),
                "valid": valid,
                "append_p99_s": round(
                    lats[min(len(lats) - 1,
                             int(len(lats) * 0.99))], 5)}

    out: dict = {"platform": jax.default_backend(), "waves": waves,
                 "block_ops": len(bw), "widths": {}}
    crossover = None
    for n in widths:
        solo = drive(n, grouped=False)
        mega_cold = drive(n, grouped=True)   # compile wall included
        mega = drive(n, grouped=True)        # the daemon steady state
        ratio = round(mega["appends_s"] / max(solo["appends_s"], 1),
                      2)
        out["widths"][str(n)] = {
            "per_session": solo, "mega": mega,
            "mega_cold_wall_s": mega_cold["wall_s"],
            "speedup": ratio,
            "p99_not_worse": (mega["append_p99_s"]
                              <= solo["append_p99_s"]),
        }
        if not (solo["valid"] and mega["valid"]):
            out["error"] = f"verdict drift at width {n}"
        if crossover is None and ratio > 1.0:
            crossover = n
    out["headline"] = out["widths"][str(max(widths))]
    if crossover is not None:
        out["crossover"] = crossover
        out["recorded"] = autotune.record(
            "session-mega", "crossover", str(crossover),
            metric=out["headline"]["speedup"],
            detail={"widths": list(widths), "waves": waves})
    return out


def txn_probe(n_txns: int, seed: int) -> dict:
    """The transactional rung (ISSUE 9): a ``n_txns`` list-append
    history (key-rotated, the real Jepsen workload shape) with one
    injected G-single block, classified end-to-end — dependency
    inference + the MXU boolean-closure engine vs the host SCC
    baseline on the SAME inferred graph. Reports agg txns/s both ways
    (warm best-of-2), the Kahn-trimmed core size the dense closure
    actually walked, and the detected anomaly classes (the injected
    class must be among them, or the rung reports an error)."""
    from jepsen_tpu import fixtures, txn
    from jepsen_tpu.txn import infer as txn_infer
    from jepsen_tpu.txn import ops as txn_ops

    t0 = time.monotonic()
    h = fixtures.gen_txn_history(n_txns, keys=6, processes=8,
                                 key_rotate=32, seed=seed)
    h = h + [op.with_(index=-1) for op in
             fixtures.txn_anomaly_block("G-single")]
    # index ONCE at composition (the anomaly block rides in with
    # index=-1): production histories arrive indexed — re-indexing
    # 2*n ops inside every timed check_history call was measuring
    # history construction, not checking
    from jepsen_tpu import history as h_mod
    h = h_mod.index(h)
    gen_s = time.monotonic() - t0
    t0 = time.monotonic()
    txns, fails = txn_ops.collect(h)
    graph = txn_infer.infer(txns, fails)
    infer_s = time.monotonic() - t0

    def best_of(fn, k=2):
        res, times = None, []
        for _ in range(k):
            t1 = time.monotonic()
            res = fn()
            times.append(time.monotonic() - t1)
        return res, min(times)

    from jepsen_tpu.txn import cycles as txn_cycles

    # the dev arm measures the SHIPPING DEFAULT body (word unless the
    # opt-out is set): bypass the autotune table so a recorded "f32"
    # winner can't silently swap the body under the "word" label below
    os.environ["JEPSEN_TPU_NO_AUTOTUNE"] = "1"
    try:
        dev, dev_s = best_of(lambda: txn.check_history(h))
        os.environ["JEPSEN_TPU_NO_WORD_CLOSURE"] = "1"
        try:
            f32, f32_s = best_of(lambda: txn.check_history(h))
        finally:
            os.environ.pop("JEPSEN_TPU_NO_WORD_CLOSURE", None)
    finally:
        os.environ.pop("JEPSEN_TPU_NO_AUTOTUNE", None)
    host, host_s = best_of(
        lambda: txn.check_history(h, force_host=True))
    # the lattice rung (ISSUE 17): every consistency level decided in
    # ONE dispatch — the K=4 ladder vs the host chain-node lattice
    # reference. (Not apples-to-apples with the serializable arm:
    # the lattice route never rides the Kahn trim, so it walks the
    # full graph where dev walks the trimmed core.)
    from jepsen_tpu.txn import lattice as txn_lattice
    all_levels = list(txn_lattice.LEVELS)
    lat, lat_s = best_of(
        lambda: txn.check_history(h, consistency=all_levels))
    lat_host, lat_host_s = best_of(
        lambda: txn.check_history(h, consistency=all_levels,
                                  force_host=True))
    out = {
        "txns": int(graph.n), "edges": int(graph.e),
        "edge_counts": graph.edge_counts(),
        "gen_s": round(gen_s, 2), "infer_s": round(infer_s, 2),
        "device": {"check_s": round(dev_s, 3),
                   "txns_s": round(graph.n / max(dev_s, 1e-9)),
                   "engine": dev.get("engine"),
                   "body": ("word" if txn_cycles.word_closure_enabled()
                            else "f32"),
                   "core_txns": dev.get("core-txns"),
                   "anomalies": dev.get("anomalies")},
        "device_f32": {"check_s": round(f32_s, 3),
                       "txns_s": round(graph.n / max(f32_s, 1e-9)),
                       "anomalies": f32.get("anomalies")},
        "host": {"check_s": round(host_s, 3),
                 "txns_s": round(graph.n / max(host_s, 1e-9)),
                 "engine": host.get("engine"),
                 "anomalies": host.get("anomalies")},
        "speedup_vs_host": round(host_s / max(dev_s, 1e-9), 2),
        "lattice": {
            "check_s": round(lat_s, 3),
            "txns_s": round(graph.n / max(lat_s, 1e-9)),
            "engine": lat.get("engine"),
            "weakest_violated": lat.get("weakest-violated"),
            "host_check_s": round(lat_host_s, 3),
            "speedup_vs_host": round(lat_host_s / max(lat_s, 1e-9),
                                     2),
            "cost_vs_serializable": round(lat_s / max(dev_s, 1e-9),
                                          2)},
        # the closure KERNEL in isolation: the e2e rung above trims
        # to a tiny core (inference dominates), so the body win is
        # measured on a closure-bound synthetic cyclic graph too,
        # and the winner lands in the autotune table warm processes
        # consult
        "closure_kernel": _closure_kernel_probe(),
    }
    if dev.get("anomalies") != host.get("anomalies") \
            or dev.get("anomalies") != f32.get("anomalies") \
            or "G-single" not in (dev.get("anomalies") or ()):
        out["error"] = (f"classification drift: device "
                        f"{dev.get('anomalies')} vs f32 "
                        f"{f32.get('anomalies')} vs host "
                        f"{host.get('anomalies')}")
    elif lat.get("holds") != lat_host.get("holds"):
        out["error"] = (f"lattice drift: device holds "
                        f"{lat.get('holds')} vs host "
                        f"{lat_host.get('holds')}")
    return out


def _closure_kernel_probe(n: int = 1024, repeat: int = 3) -> dict:
    """Word-packed vs f32 closure bodies on a closure-BOUND graph
    (random cyclic, no trimmable fringe at this density): the kernel
    comparison the 100k rung's tiny trimmed core can't show. Records
    the winner in the autotune table (tools/closure_sweep.py is the
    full sweep; this keeps BENCH honest about the body in one run)."""
    import numpy as np

    from jepsen_tpu.checkers import autotune
    from jepsen_tpu.txn import cycles
    from jepsen_tpu.txn.infer import DepGraph

    r = np.random.default_rng(42)
    e = n * 2
    src = r.integers(0, n, e).astype(np.int32)
    dst = r.integers(0, n, e).astype(np.int32)
    keep = src != dst
    g = DepGraph(n=n, src=src[keep], dst=dst[keep],
                 et=r.integers(0, 3, int(keep.sum())).astype(np.int8),
                 txns=tuple(range(n)))

    def _t(no_word: bool) -> float:
        env = "JEPSEN_TPU_NO_WORD_CLOSURE"
        at = "JEPSEN_TPU_NO_AUTOTUNE"
        old = os.environ.pop(env, None)
        old_at = os.environ.pop(at, None)
        try:
            # a recorded winner must not steer the arm being measured
            os.environ[at] = "1"
            if no_word:
                os.environ[env] = "1"
            cycles.closure_booleans(g)          # warm
            best = float("inf")
            for _ in range(repeat):
                t0 = time.monotonic()
                cycles.closure_booleans(g)
                best = min(best, time.monotonic() - t0)
            return best
        finally:
            os.environ.pop(env, None)
            os.environ.pop(at, None)
            if old is not None:
                os.environ[env] = old
            if old_at is not None:
                os.environ[at] = old_at

    w, f = _t(False), _t(True)
    winner = "word" if w <= f else "f32"
    autotune.record("closure", autotune.closure_key(n), winner,
                    metric=1.0 / max(min(w, f), 1e-9))
    return {"Np": n, "word_s": round(w, 4), "f32_s": round(f, 4),
            "winner": winner,
            "speedup": round(f / max(w, 1e-9), 2)}


def walk_bodies_probe(model, packed, n_ops: int,
                      repeat: int = 2) -> dict:
    """The post-hoc kernel-body comparison on the headline history:
    ``reach.check_packed`` with the word-packed body FORCED vs the
    dense/pallas chain, verdicts asserted equal, winner recorded in
    the autotune table (``walk`` kind) that route selection consults
    on the next process. The 33x XLA:CPU step-cost folklore becomes a
    measured, persisted number."""
    from jepsen_tpu.checkers import autotune, events as ev, reach

    memo, stream, _T, S_pad, M = reach._prep(
        model, packed, max_states=100_000, max_slots=20,
        max_dense=1 << 22)
    W = max(stream.W, 1)
    rs = ev.returns_view(stream)

    def _t(body: str):
        env = ("JEPSEN_TPU_WORD_POSTHOC" if body == "word"
               else "JEPSEN_TPU_NO_WORD_WALK")
        old = os.environ.pop(env, None)
        os.environ[env] = "1"
        try:
            res = reach.check_packed(model, packed)     # warm
            best = float("inf")
            for _ in range(max(1, repeat)):
                t0 = time.monotonic()
                res = reach.check_packed(model, packed)
                best = min(best, time.monotonic() - t0)
            return res, best
        finally:
            os.environ.pop(env, None)
            if old is not None:
                os.environ[env] = old

    res_w, t_w = _t("word")
    res_d, t_d = _t("dense")
    out = {"geometry": {"S": memo.n_states, "W": W, "M": M,
                        "returns": int(rs.n_returns)},
           "word": {"check_s": round(t_w, 3),
                    "ops_s": round(n_ops / max(t_w, 1e-9)),
                    "engine": res_w.get("engine")},
           "dense": {"check_s": round(t_d, 3),
                     "ops_s": round(n_ops / max(t_d, 1e-9)),
                     "engine": res_d.get("engine")},
           "speedup_word_vs_dense": round(t_d / max(t_w, 1e-9), 2)}
    if res_w.get("valid") != res_d.get("valid"):
        out["error"] = (f"verdict drift: word {res_w.get('valid')} "
                        f"vs dense {res_d.get('valid')}")
        return out
    winner = "word" if t_w <= t_d else "dense"
    out["winner"] = winner
    out["recorded"] = autotune.record(
        "walk", autotune.walk_key(memo.n_states, W, M, rs.n_returns),
        winner, metric=n_ops / max(min(t_w, t_d), 1e-9))
    return out


def _ragged_lengths(total: int, keys: int = 12,
                    ratio: float = 1.45) -> list:
    """Deterministic mixed-length key split (BASELINE config #4 shape):
    a geometric spread over ``keys`` keys summing to ~``total`` ops, so
    lengths span several power-of-two buckets and the bucketed lane
    packer has real work to do."""
    w = [ratio ** -i for i in range(keys)]
    s = sum(w)
    return [max(24, int(total * x / s)) for x in w]


def independent_probe(model, n_ops: int, seed: int,
                      processes: int) -> dict:
    """Ragged independent-keys rung: ``n_ops`` total over >= 8 keys of
    mixed lengths through ``reach.check_many`` (the bucketed LOCKSTEP
    lane by default on TPU), against the sequential per-key
    ``check_packed`` baseline measured in the same run — the honest
    apples-to-apples the acceptance bar asks for. Reports per-bucket
    geometry, pack efficiency, kernel-cache counters, and aggregate
    ops/s for both paths."""
    from jepsen_tpu import fixtures
    from jepsen_tpu.checkers import reach

    lens = _ragged_lengths(n_ops)
    packeds = [fixtures.gen_packed("cas", n_ops=L, processes=processes,
                                   seed=seed + 500 + i)
               for i, L in enumerate(lens)]
    total = sum(lens)
    diag: dict = {}
    res = reach.check_many(model, packeds, diag=diag)   # warm/compile
    if not all(r["valid"] is True for r in res):
        return {"error": "bad ragged verdicts"}
    engines = sorted({r["engine"] for r in res})
    times = []
    best_diag = diag
    for _ in range(2):
        d: dict = {}
        t1 = time.monotonic()
        reach.check_many(model, packeds, diag=d)
        dt = time.monotonic() - t1
        if not times or dt < min(times):
            best_diag = d or diag
        times.append(dt)
    best = min(times)
    # sequential per-key baseline: same histories, same run, warmed
    # once, and timed with the SAME best-of-2 discipline as the batch
    # side so speedup_vs_sequential compares like with like
    for p in packeds:
        reach.check_packed(model, p)
    seq_times = []
    for _ in range(2):
        t1 = time.monotonic()
        for p in packeds:
            reach.check_packed(model, p)
        seq_times.append(time.monotonic() - t1)
    seq_s = max(min(seq_times), 1e-9)
    prep = best_diag.get("prep", {})
    mesh = best_diag.get("mesh") or {}
    return {"keys": len(lens), "lens": lens,
            "e2e_s": round(best, 3),
            "agg_ops_s": round(total / best),
            "seq_s": round(seq_s, 3),
            "seq_ops_s": round(total / seq_s),
            "speedup_vs_sequential": round(seq_s / best, 2),
            "engine": engines,
            "n_devices": mesh.get("n_devices", 1),
            "per_device_groups": mesh.get("per_device_groups"),
            "mesh_pad_lanes": mesh.get("pad_lanes", 0),
            "prep_s": prep.get("wall_s"),
            "prep_hidden_s": prep.get("hidden_s"),
            "prep_mode": prep.get("mode"),
            "dispatch_s": best_diag.get("dispatch_s"),
            "fetch_s": best_diag.get("fetch_s"),
            "transfer": best_diag.get("transfer"),
            "pack_efficiency": best_diag.get("pack_efficiency"),
            "real_returns": best_diag.get("real_returns"),
            "padded_returns": best_diag.get("padded_returns"),
            "kernel_cache": best_diag.get("kernel_cache"),
            "per_bucket": best_diag.get("groups", [])}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=100_000)
    ap.add_argument("--processes", type=int, default=5)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--engine", default="reach",
                    choices=["reach", "chunked", "batch", "wgl-cpu",
                             "wgl-native"])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--no-batch", action="store_true",
                    help="skip the lockstep batch probe")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="write a jax.profiler trace of one steady-state "
                         "check to DIR")
    ap.add_argument("--trace", metavar="PATH", default="trace.json",
                    help="write the obs span trace (Chrome trace_event "
                         "JSON; '' disables)")
    ap.add_argument("--quick", action="store_true",
                    help="small/CI run: caps --ops at 20k, one repeat, "
                         "skips the batch probe — the transfer-guard "
                         "CI step's configuration")
    ap.add_argument("--serve", action="store_true",
                    help="append the 'serve' sub-object: an "
                         "in-process check daemon driven by the "
                         "open-loop load generator (req/s, p50/p99 "
                         "verdict latency)")
    ap.add_argument("--txn", action="store_true",
                    help="append the 'txn' sub-object: the "
                         "transactional rung — a --ops-txn "
                         "list-append history with an injected "
                         "anomaly, MXU closure vs host SCC "
                         "(agg txns/s both ways)")
    args = ap.parse_args()
    if args.quick:
        args.ops = min(args.ops, 20_000)
        args.repeat = 1
        args.no_batch = True

    from jepsen_tpu import fixtures, models, obs, store
    from jepsen_tpu.checkers import reach, wgl_ref

    # persistent compilation cache (ISSUE 3): a cold second process
    # re-running bench.py loads every kernel geometry from disk instead
    # of recompiling — first-iteration latency drops and
    # compile_cache.hits > 0 lands in the output. JEPSEN_TPU_NO_PERSIST=1
    # reverts to cacheless runs.
    cc_dir = store.enable_compilation_cache()

    def _finish(out: dict, probe_engine) -> None:
        # the bench selects its engine explicitly — record it in the
        # ledger so the obs sub-object names what was measured, then
        # attach the counters/ledger snapshot and write the trace
        obs.decision(str(probe_engine or args.engine), "selected",
                     cause="bench-cli", ops=args.ops)
        out.update(_device_info())
        snap = obs.snapshot()
        out["obs"] = snap
        counters = snap.get("counters", {})
        out["compile_cache"] = {
            "dir": cc_dir,
            "hits": int(counters.get("compile_cache.hits", 0)),
            "requests": int(counters.get("compile_cache.requests", 0)),
        }
        if args.trace:
            try:
                out["trace_file"] = obs.export_trace(args.trace)
            except OSError as e:
                out["trace_file"] = f"error: {e}"

    if args.engine == "batch":
        # the batch dimension AS the headline: ragged independent-keys
        # through the bucketed lockstep lane, vs the sequential
        # per-key baseline in the same run
        model = models.cas_register()
        with obs.span("bench.independent_probe", ops=args.ops):
            probe = independent_probe(model, args.ops, args.seed,
                                      args.processes)
        agg = probe.get("agg_ops_s", 0) or 0
        baseline_floor = 100_000 / 60.0
        out = {"metric": (f"independent-{args.ops // 1000}k-cas-"
                          f"x{probe.get('keys', 0)}"),
               "value": float(agg), "unit": "ops/s",
               "vs_baseline": round(agg / baseline_floor, 2),
               "batch": probe}
        _finish(out, (probe.get("engine") or ["reach-many"])[0])
        print(json.dumps(out))
        return 0 if "error" not in probe else 1

    t0 = time.monotonic()
    # native packed-level generation: at 10M ops the Python tick loop
    # plus Op/Entry materialization took ~224 s — the C++ simulation
    # emits the packed arrays directly in <1 s (same construction, so
    # still linearizable by definition)
    packed = fixtures.gen_packed("cas", n_ops=args.ops,
                                 processes=args.processes, seed=args.seed)
    gen_s = time.monotonic() - t0
    model = models.cas_register()

    def run():
        if args.engine == "reach":
            return reach.check_packed(model, packed)
        if args.engine == "chunked":
            return reach.check_chunked(model, packed=packed)
        if args.engine == "wgl-native":
            from jepsen_tpu.checkers import wgl_native
            return wgl_native.check_packed(model, packed)
        return wgl_ref.check_packed(model, packed, time_limit=300)

    # warm-up: first call pays jit compilation (or a persistent-cache
    # load on a warm start — first_iter_s in the output is the number
    # that drops when compile_cache.hits > 0); the measurement is
    # steady state (compile caches persist across runs of the same
    # shapes).
    t1 = time.monotonic()
    with obs.span("bench.warm", engine=args.engine, ops=args.ops):
        res = run()
    first_iter_s = time.monotonic() - t1
    if res["valid"] is not True:
        # the ledger explaining WHICH engine produced the bad verdict
        # (and what fell back en route) ships with the error too
        out = {"metric": "linearize-100k-cas",
               "value": 0.0, "unit": "ops/s",
               "vs_baseline": 0.0,
               "error": f"bad verdict {res.get('valid')}"}
        _finish(out, res.get("engine"))
        print(json.dumps(out))
        return 1
    times = []
    if args.profile:
        # SURVEY.md §5 tracing: a jax.profiler trace of the steady-state
        # solver, viewable in TensorBoard / Perfetto
        import jax
        with jax.profiler.trace(args.profile):
            t1 = time.monotonic()
            res = run()
            times.append(time.monotonic() - t1)
    for i in range(max(1, args.repeat)):
        t1 = time.monotonic()
        with obs.span("bench.measure", engine=args.engine, rep=i):
            res = run()
        times.append(time.monotonic() - t1)
    best = min(times)
    ops_per_s = args.ops / best
    baseline_floor = 100_000 / 60.0
    out = {
        "metric": f"linearize-{args.ops // 1000}k-cas",
        "value": round(ops_per_s, 1),
        "unit": "ops/s",
        "vs_baseline": round(ops_per_s / baseline_floor, 2),
        "check_s": round(best, 3),
        "first_iter_s": round(first_iter_s, 3),
        "gen_s": round(gen_s, 2),
        "engine": res.get("engine"),
        "valid": res.get("valid"),
        "events": res.get("events"),
        "slots": res.get("slots"),
    }
    if args.engine == "reach":
        # both probes measure the same lane operand set: prep it once
        probe_prep, probe_prep_s = None, None
        try:
            t_pp = time.monotonic()
            probe_prep = _lane_operands(model, packed)
            probe_prep_s = time.monotonic() - t_pp
        except Exception:                               # noqa: BLE001
            pass        # each probe reports its own failure below
        try:
            # host-only marshalling breakdown — works on CPU-only CI,
            # where the device probes below skip; the transfer-guard
            # step budgets these numbers
            out["transfer"] = transfer_probe(model, packed,
                                             prep=probe_prep)
        except Exception as e:                          # noqa: BLE001
            out["transfer"] = {"error": f"{type(e).__name__}: {e}"}
        # the two Pallas probes measure compiled-kernel timings: on the
        # CPU backend Pallas only runs in interpret mode, whose
        # timings would be misleading — a structured skip, never a raw
        # exception string in the bench JSON (BENCH r08 regression)
        pallas_cpu = _pallas_needs_accelerator()
        if pallas_cpu:
            out["kernel"] = {"skipped": "pallas-needs-accelerator"}
        else:
            try:
                out["kernel"] = kernel_probe(model, packed,
                                             prep=probe_prep,
                                             prep_s=probe_prep_s)
            except Exception as e:                      # noqa: BLE001
                # probe is diagnostics, not the metric: histories the
                # lane kernel does not admit skip it
                out["kernel"] = {"error": f"{type(e).__name__}: {e}"}
        if pallas_cpu:
            out["chunklock"] = {"skipped": "pallas-needs-accelerator"}
        else:
            try:
                out["chunklock"] = chunklock_probe(model, packed)
            except Exception as e:                      # noqa: BLE001
                out["chunklock"] = {"error":
                                    f"{type(e).__name__}: {e}"}
        try:
            # post-hoc kernel BODIES on this rung's history: the
            # word-packed walk vs the dense/pallas chain, winner
            # persisted in the autotune table (warm processes then
            # route check_packed through the recorded winner)
            out["walk_bodies"] = walk_bodies_probe(model, packed,
                                                   args.ops)
        except Exception as e:                          # noqa: BLE001
            out["walk_bodies"] = {"error":
                                  f"{type(e).__name__}: {e}"}
        if not args.no_batch and args.ops <= 200_000:
            try:
                out["batch"] = batch_probe(model, args.ops, args.seed,
                                           args.processes)
            except Exception as e:                      # noqa: BLE001
                out["batch"] = {"error": f"{type(e).__name__}: {e}"}
    if args.serve:
        try:
            with obs.span("bench.serve_probe"):
                out["serve"] = serve_probe(quick=args.quick)
        except Exception as e:                          # noqa: BLE001
            out["serve"] = {"error": f"{type(e).__name__}: {e}"}
        try:
            # the streaming-session rung rides --serve: sustained
            # appends/s + p99 append-to-verdict vs the host online
            # monitor on the same op stream
            with obs.span("bench.session_probe"):
                out["session"] = session_probe(
                    n_ops=min(args.ops, 100_000), seed=args.seed,
                    quick=args.quick)
        except Exception as e:                          # noqa: BLE001
            out["session"] = {"error": f"{type(e).__name__}: {e}"}
        try:
            # the multiplexing rung (ISSUE 16): L live streams, one
            # vmapped launch per wave vs L per-session launches
            with obs.span("bench.session_mux_probe"):
                out["session_mux"] = session_mux_probe(
                    quick=args.quick)
        except Exception as e:                          # noqa: BLE001
            out["session_mux"] = {"error":
                                  f"{type(e).__name__}: {e}"}
    if args.txn:
        try:
            with obs.span("bench.txn_probe", txns=args.ops):
                out["txn"] = txn_probe(args.ops, args.seed)
        except Exception as e:                          # noqa: BLE001
            out["txn"] = {"error": f"{type(e).__name__}: {e}"}
    _finish(out, res.get("engine"))
    print(json.dumps(out))
    if out["platform"] == "tpu" and _probe_errors(out):
        # on the chip every probe is a measurement: one that errored
        # fails the run instead of hiding in a sub-object
        print(f"probe errors: {_probe_errors(out)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
