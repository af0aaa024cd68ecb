"""Drive a cell on the CPU at a small size: the harness's look for a
chip skipped, the configuration and the mix shrunk, everything else as
in a run. For the tests under ``benchmark/tests`` (not tier-1).

A file is shrunk by its entry in :data:`SMALL`, then by the ``"small"``
object it carries itself, so a configuration or a traffic mix that a
later change adds brings its own CPU sizes."""
from __future__ import annotations

import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import run  # noqa: E402

SMALL = {
    "long": {"ops": 3000, "pool": 3, "corrupted": [1], "sample": 3},
    "batch": {"pool_batches": 3, "warmup": 1, "sample_keys": 48},
    "serve": {"rate": 12.0, "drain_s": 60, "sample": 24},
    "cas-independent": {"keys_per_chip": 48, "ops_per_key": 60},
}


def run_small(cell: str, seconds: float = 1.0, seed: int = 2**31 + 7,
              trace: int = 0, control: int = 0, chips=None,
              bench=None) -> dict:
    """One run of ``cell`` at its small size; ``bench`` in place of
    ``BENCHMARK.json`` (a copy with cells added, say)."""
    bench = run.load_json("BENCHMARK.json") if bench is None else bench
    real = run.load_json

    def shrunk(*parts):
        d = real(*parts)
        name = os.path.splitext(os.path.basename(parts[-1]))[0]
        d.update(SMALL.get(name, {}))
        d.update(d.pop("small", {}))
        return d

    if chips is not None:
        bench = json.loads(json.dumps(bench))
        for w in bench["workloads"]:
            if w["name"] == cell:
                w["chips"] = chips
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace),
                      "--control", str(control)])
    run.load_json = shrunk
    try:
        return run.run_cell(args, bench, allow_cpu=True, log=io.StringIO())
    finally:
        run.load_json = real
