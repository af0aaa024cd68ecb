"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration in ``benchmark/configs/<config>.json`` (whose
``family``, ``cas`` when it names none, is the kind of history it
checks: ``benchmark/families/<family>.py``), its traffic mix in
``benchmark/traffic/<traffic>.json`` (whose ``entry`` names one of
the drivers in ``drivers.py``), each metric's reader in
``benchmark/metrics/<metric>.py`` and the peaks in
``benchmark/peaks.json``. With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window runs under
the profiler and the result carries its per-layer metrics.

The last line of stdout is the result, printed only when the run got
that far; the last lines of stderr are the numbers compared with the
reference, each beside its limit.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# JAX's compile cache is the program's fixed <checkout>/.cache/xla
# (``store.enable_compilation_cache``), never a directory the machine
# names for every checkout alike.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

# the limit of each number compared: an exact comparison, limit 0
LIMITS = {"verdict_mismatch": 0, "failing_op_mismatch": 0}


class NoChip(RuntimeError):
    pass


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def reader(name: str):
    """The ``read(ctx)`` function of metric ``name``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


class CompileClock:
    """Counts JAX's backend compiles (``jax.monitoring``; copied from
    ``chip_smoke.py``)."""

    def __init__(self) -> None:
        import jax
        self.count, self.total = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.total += duration
            self.count += 1


class Run:
    def __init__(self, args, bench: dict, allow_cpu: bool = False):
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.rate = getattr(args, "rate", None)
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise KeyError(f"no workload {args.workload!r}")
        self.cell = cells[args.workload]
        self.chips = int(self.cell["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(configs[self.cell["config"]]["file"])
        self.traffic = load_json("benchmark", "traffic",
                                 self.cell["traffic"] + ".json")
        import jax
        devs = jax.devices()
        if not allow_cpu and (devs[0].platform == "cpu"
                              or len(devs) < self.chips):
            raise NoChip(f"{len(devs)} {devs[0].platform} device(s); "
                         f"the cell needs {self.chips} accelerator(s)")
        self.devices = devs[:self.chips]


# entries the program's disk memo tier holds before it evicts
# (``reach._DISK_MEMO_MAX_ENTRIES``)
MEMO_TIER_CAP = 512


def fill_memo_tier() -> None:
    """Start every run with the program's disk memo tier at its cap:
    the state a checker reaches after a few hundred distinct op
    alphabets, in which every new entry pays the tier's eviction scan.
    Without it a fresh checkout's first runs skip that scan and read
    faster than the rest. The placeholders are empty, match no
    signature, and are the oldest entries, so the program evicts them
    first. The entries earlier runs left are removed first: a run of a
    seed seen before would hit them and skip the put."""
    from jepsen_tpu import store
    root = store.persist_root()
    if root is None:
        return
    d = os.path.join(root, "memo")
    os.makedirs(d, exist_ok=True)
    have = set()
    for n in os.listdir(d):
        if n.startswith("bench-fill-"):
            have.add(n)
        elif n.endswith(".memo.pkl"):
            os.remove(os.path.join(d, n))
    k = 0
    while len(have) < MEMO_TIER_CAP:
        name = f"bench-fill-{k:04d}.memo.pkl"
        k += 1
        if name in have:
            continue
        path = os.path.join(d, name)
        open(path, "wb").close()
        os.utime(path, (0, 0))
        have.add(name)


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:                               # noqa: BLE001
            pass
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def obs_spans(t0_us: float, t1_us: float) -> list:
    from jepsen_tpu.obs import core
    return [e for e in core.GLOBAL.span_events()
            if e["ts"] < t1_us and e["ts"] + e["dur"] > t0_us]


class Tracer:
    """The profiler over the window, or over its first ``trace_s``
    seconds (a mix whose device runs many small ops per second fills
    the device's trace buffer in a few seconds). Called as the
    annotation factory by the drivers; ``tick(t)`` after each check
    stops the trace once ``trace_s`` has passed. The Python tracer is
    off: it slows every call and fills the trace."""

    def __init__(self, on: bool, trace_s=None) -> None:
        self.on, self.trace_s = on, trace_s
        self.dir = None
        self.t0 = self.t1 = 0.0
        self.w1_us = None
        self.cut = False
        self._win = None

    def __call__(self, name: str):
        if self.on and self._win is not None:
            import jax
            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def start(self) -> None:
        if self.on:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._win = jax.profiler.TraceAnnotation("bench.window")
            self._win.__enter__()
        self.t0 = time.perf_counter()

    def tick(self, t: float) -> None:
        if (self._win is not None and self.trace_s is not None
                and t - self.t0 >= self.trace_s):
            self.cut = True
            self.stop()

    def stop(self) -> None:
        if self._win is None:
            return
        import jax
        from jepsen_tpu.obs import core
        self._win.__exit__(None, None, None)
        self.t1, self.w1_us = time.perf_counter(), core._now_us()
        self._win = None
        jax.profiler.stop_trace()


def run_cell(args, bench: dict, allow_cpu: bool = False,
             log=sys.stderr) -> dict:
    """One run; returns the result object (the caller prints it)."""
    from benchmark import drivers, trace_reduce

    run = Run(args, bench, allow_cpu=allow_cpu)
    import jax

    from jepsen_tpu import obs, store
    if store.enable_compilation_cache() != os.path.join(ROOT, ".cache",
                                                        "xla"):
        raise RuntimeError("the compile cache is not in the checkout")
    # unbounded: a run writes tens of MB, and the bounded cache's
    # eviction fails every write once one entry lacks its access time
    jax.config.update("jax_compilation_cache_max_size", -1)
    clock = CompileClock()
    fill_memo_tier()
    driver = drivers.DRIVERS[run.traffic["entry"]](run)
    driver.setup()
    gc.collect()                        # the set-up's garbage, not the window's
    setup_s = time.perf_counter() - T_START

    tracer = Tracer(bool(args.trace), run.traffic.get("trace_s"))
    obs.reset()
    n_compiles = clock.count
    w0_us = obs.core._now_us()
    tracer.start()
    driver.window(run.seconds, tracer)
    tracer.stop()
    w1_us = tracer.w1_us or obs.core._now_us()
    in_window = clock.count - n_compiles
    driver.after_window()
    device = device_info(run.devices)
    tracing = tracer.on
    # a traced run reads its per-layer metrics over the traced part
    records = [r for r in driver.records
               if not tracing or r.get("t0", 0.0) < tracer.t1]
    window_s = ((tracer.t1 - tracer.t0) if tracing and tracer.cut
                else driver.window_s)
    spans = obs_spans(w0_us, w1_us)

    ctx = {"run": run, "driver": driver, "setup_s": setup_s,
           "window_s": window_s, "records": records,
           "spans": spans, "obs_window_us": (w0_us, w1_us),
           "trace": None, "peaks": None}
    breakdown = None
    if tracing:
        red = trace_reduce.reduce_file(
            trace_reduce.find_xplane(tracer.dir))
        shutil.rmtree(tracer.dir, ignore_errors=True)
        ctx["trace"] = red
        busy = [d["busy_s"] for d in red["devices"]][:run.chips]
        device["busy_s"] = sum(busy) / max(1, len(busy))
        device["window_s"] = red["window_s"]
        # the program's spans, moved onto the trace's clock
        off_ns = red["window"][0] - w0_us * 1e3
        prog = [(s["name"], s["ts"] * 1e3 + off_ns,
                 (s["ts"] + s["dur"]) * 1e3 + off_ns) for s in spans]
        breakdown = {"device_ops": trace_reduce.top_ops(red),
                     "idle_gaps": trace_reduce.label_gaps(red, prog)}
        peaks = load_json("benchmark", "peaks.json")["devices"]
        ctx["peaks"] = peaks.get(device["kind"])
    driver.stop()

    kind = "per_layer" if tracing else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, run.cell["name"], kind):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    print(f"benchmark: setup_s {setup_s} window_s {driver.window_s} "
          f"compiles_in_window {in_window} checks {len(driver.records)}",
          file=log)
    for line in getattr(driver, "late_line", []):
        print(f"benchmark: {line}", file=log)
    if hasattr(driver, "backlog"):
        print(f"benchmark: backlog {json.dumps(driver.backlog())}",
              file=log)
    numbers = drivers.compare(driver.fam, driver.checks(),
                              control=bool(getattr(args, "control", 0)))
    compared = {k: {"value": v, "limit": LIMITS[k]}
                for k, v in numbers.items()}
    correct = all(v <= LIMITS[k] for k, v in numbers.items())
    for k, v in numbers.items():
        print(f"compared {k} {v} limit {LIMITS[k]}", file=log)
    out = {"correct": correct, "attempted": driver.attempted(),
           "failed": driver.failed(), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = compared
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # builder's tools, never passed by the driver: the serve sweep's
    # offered rate, and the control in the program's place
    ap.add_argument("--rate", type=float, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--control", type=int, default=0,
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = load_json("BENCHMARK.json")
        out = run_cell(args, bench)
    except NoChip as e:
        print(f"benchmark: no accelerator: {e}", file=sys.stderr)
        return 3
    except Exception as e:                              # noqa: BLE001
        import traceback
        traceback.print_exc()
        print(f"benchmark: failed: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
