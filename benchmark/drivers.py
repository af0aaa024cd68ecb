"""The three ways a cell drives the program, chosen by the traffic
mix's ``entry``. Each driver builds its inputs from the seed during
set-up, warms the cell's shapes, runs the window, and afterwards hands
the harness its records and the sample that the reference checks.
What a driver knows of the histories themselves (how they are made,
corrupted, relabelled, packed, counted and checked) comes from the
configuration's history family, ``benchmark/families/<family>.py``
(``cas`` when the configuration names none).

- ``check_packed``: closed loop, one history in flight, through
  ``reach.check_packed``;
- ``check_many``: closed loop, one keyed batch in flight, through
  ``reach.check_many`` on the cell's chips;
- ``serve``: open loop over loopback HTTP into an in-process
  ``serve.Daemon``, the client a child process (``loadgen.py``).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import sut, work

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_FAMILY = "cas"


def load_family(name: str):
    """The history family ``name``: the module
    ``benchmark/families/<name>.py``, loaded by path (a name is a path
    relative to that directory, without ``.py``)."""
    path = os.path.join(HERE, "families", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_family_" + "".join(
            c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _perm_order(rng, values: int) -> List[int]:
    """Every non-identity value permutation, in a seeded order."""
    n = math.factorial(values)
    return [int(x) for x in rng.permutation(np.arange(1, n))]


class Driver:
    """What every driver gives the harness."""

    def __init__(self, run) -> None:
        self.run = run
        self.cfg, self.mix = run.config, run.traffic
        self.fam = load_family(self.cfg.get("family", DEFAULT_FAMILY))
        self.rng = np.random.default_rng(run.seed)
        self.records: List[Dict[str, Any]] = []
        self.window_s = 0.0

    # annotate: the harness's tracer: a factory of host annotations,
    # and ``annotate.tick(t)`` after each check
    def window(self, seconds: float, annotate) -> None:
        raise NotImplementedError

    def attempted(self) -> int:
        return sum(r["histories"] for r in self.records)

    def failed(self) -> int:
        return sum(r["failed"] for r in self.records)

    def checks(self) -> List[tuple]:
        """(History, program answer) pairs for the reference."""
        raise NotImplementedError

    def after_window(self) -> None:
        pass

    def stop(self) -> None:
        pass


def _check_failed(res: dict, bad_before: dict) -> bool:
    return (not sut.on_device(res) or "error" in res
            or sut.bad_counters() != bad_before)


class CheckPacked(Driver):
    """A pool of long histories (``pool`` of them, the ones listed in
    ``corrupted`` made invalid), each check taking pool entry i mod
    pool under a value permutation not used before in the run."""

    def setup(self) -> None:
        c, m = self.cfg, self.mix
        n, pool = int(m["ops"]), int(m["pool"])
        self.bases = []
        for i in range(pool):
            h = self.fam.history(self.rng, c, n)
            if i in m["corrupted"]:
                h = self.fam.corrupt_at(self.rng, h, m.get("corrupt_at"))
            self.bases.append(h)
        self.packed = [self.fam.packer(h) for h in self.bases]
        self.work = [self.fam.work(h) for h in self.bases]
        self.perms = _perm_order(self.rng, c["values"])
        for p in self.packed:                        # the cell's shapes
            self.fam.check_packed(p.packed(None))

    def _perm(self, i: int):
        pool = len(self.bases)
        k = i // pool
        return self.fam.perm_of(self.perms[k % len(self.perms)],
                                self.cfg["values"])

    def window(self, seconds: float, annotate) -> None:
        pool = len(self.bases)
        t_start = time.perf_counter()
        end = t_start + seconds
        i = 0
        while True:
            b, perm = i % pool, self._perm(i)
            packed = self.packed[b].packed(perm)
            bad = sut.bad_counters()
            with annotate("bench.check"):
                t0 = time.perf_counter()
                try:
                    res = self.fam.check_packed(packed)
                except Exception as e:                  # noqa: BLE001
                    res = {"error": repr(e)}
                t1 = time.perf_counter()
            self.records.append({
                "t0": t0, "t1": t1, "base": b, "perm": perm,
                "ops": self.bases[b].n_ops, "histories": 1,
                "work": self.work[b], "answer": sut.answer(res),
                "engine": res.get("engine"),
                "failed": int(_check_failed(res, bad))})
            annotate.tick(t1)
            i += 1
            if t1 >= end or annotate.cut:
                break
        self.window_s = self.records[-1]["t1"] - t_start

    def checks(self) -> List[tuple]:
        m = self.mix
        pool = len(self.bases)
        idx = list(range(len(self.records)))
        bad = [i for i in idx if i % pool in m["corrupted"]][:1]
        rest = [i for i in idx if i not in bad]
        k = max(0, int(m["sample"]) - len(bad))
        pick = bad + sorted(self.rng.choice(rest, min(k, len(rest)),
                                            replace=False).tolist())
        out = []
        for i in pick:
            r = self.records[i]
            out.append((self.fam.relabel(self.bases[r["base"]], r["perm"]),
                        r["answer"]))
        return out


class CheckMany(Driver):
    """Keyed batches of ``keys_per_chip`` x chips histories, each key
    of ``ops_per_key`` ops, every ``corrupt_every``-th key invalid.
    Batch j rotates the key order by a seeded shift and relabels every
    key's values by one permutation not used before, so no batch
    repeats."""

    def setup(self) -> None:
        c, m = self.cfg, self.mix
        n_keys = int(c["keys_per_chip"]) * self.run.chips
        self.bases = []
        for k in range(n_keys):
            h = self.fam.history(self.rng, c, int(c["ops_per_key"]))
            if k % int(c["corrupt_every"]) == int(c["corrupt_offset"]):
                h = self.fam.corrupt_at(self.rng, h, None)
            self.bases.append(h)
        self.packed = [self.fam.packer(h) for h in self.bases]
        w: Dict[str, float] = {}
        for h in self.bases:
            w = work.add(w, self.fam.work(h))
        self.work = w
        self.devices = self.run.devices if self.run.chips > 1 else None
        perms = _perm_order(self.rng, c["values"])
        self.batches = []
        for j in range(int(m["pool_batches"]) + int(m["warmup"])):
            shift = int(self.rng.integers(0, n_keys))
            perm = self.fam.perm_of(perms[j % len(perms)], c["values"])
            order = [(k + shift) % n_keys for k in range(n_keys)]
            self.batches.append((order, perm, [
                self.packed[k].packed(perm) for k in order]))
        for j in range(int(m["warmup"])):
            self.fam.check_many(self.batches.pop()[2], devices=self.devices)

    def window(self, seconds: float, annotate) -> None:
        t_start = time.perf_counter()
        end = t_start + seconds
        j = 0
        while True:
            order, perm, batch = self.batches[j % len(self.batches)]
            if j == len(self.batches):
                print("benchmark: batch pool exhausted; batches repeat",
                      file=sys.stderr)
            bad = sut.bad_counters()
            diag: dict = {}
            with annotate("bench.check"):
                t0 = time.perf_counter()
                try:
                    res = self.fam.check_many(batch, devices=self.devices,
                                              diag=diag)
                except Exception as e:                  # noqa: BLE001
                    res = [{"error": repr(e)}] * len(batch)
                t1 = time.perf_counter()
            grew = sut.bad_counters() != bad
            answers = [sut.answer(r) for r in res]
            failed = sum(1 for r in res if grew or not sut.on_device(r)
                         or "error" in r)
            self.records.append({
                "t0": t0, "t1": t1, "order": order, "perm": perm,
                "ops": sum(self.bases[k].n_ops for k in order),
                "histories": len(batch), "work": self.work,
                "answers": answers, "diag": diag,
                "engines": sorted({str(r.get("engine")) for r in res}),
                "failed": failed})
            annotate.tick(t1)
            j += 1
            if t1 >= end or annotate.cut:
                break
        self.window_s = self.records[-1]["t1"] - t_start

    def checks(self) -> List[tuple]:
        c, m = self.cfg, self.mix
        every, off = int(c["corrupt_every"]), int(c["corrupt_offset"])
        out = []
        n = int(m["sample_keys"])
        for _ in range(n):
            j = int(self.rng.integers(0, len(self.records)))
            r = self.records[j]
            pos = int(self.rng.integers(0, len(r["order"])))
            if len(out) % 4 == 0:       # a quarter from the invalid keys
                while r["order"][pos] % every != off:
                    pos = (pos + 1) % len(r["order"])
            k = r["order"][pos]
            out.append((self.fam.relabel(self.bases[k], r["perm"]),
                        r["answers"][pos]))
        return out


def _post(url: str, body: str):
    """POST /check from the harness (warm-up only); the request id."""
    import urllib.request
    req = urllib.request.Request(
        url + "/check", data=body.encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read()).get("id")


class Serve(Driver):
    """Open-loop Poisson arrivals of one-shot ``POST /check`` requests.
    Every seed sends the same work in its own order: the gaps are the
    quantiles of the exponential distribution at the mix's rate, the
    sizes cycle through ``sizes``, one in ``corrupt_every`` requests of
    each size is invalid (corrupted at ``corrupt_at`` of its length),
    tenants take turns."""

    def _requests(self, n: int) -> list:
        """``n`` requests as (due offset, History, invalid)."""
        c, m = self.cfg, self.mix
        rate = float(self.run.rate or m["rate"])
        q = (np.arange(n) + 0.5) / n
        gaps = self.rng.permutation(-np.log1p(-q) / rate)
        sizes = np.resize(np.asarray(m["sizes"], np.int64), n)
        bad = ((np.arange(n) // len(m["sizes"])) % int(c["corrupt_every"])
               == int(c["corrupt_offset"]))
        order = self.rng.permutation(n)
        out, due = [], 0.0
        for i in range(n):
            due += float(gaps[i])
            h = self.fam.history(self.rng, c, int(sizes[order[i]]))
            if bad[order[i]]:
                h = self.fam.corrupt_at(self.rng, h, m["corrupt_at"])
            out.append((due, h, bool(bad[order[i]])))
        return out

    def _body(self, i: int, h) -> str:
        return json.dumps({"model": self.fam.model_name,
                           "tenant": f"t{i % int(self.mix['tenants'])}",
                           "history": self.fam.to_ops(h)})

    def setup(self) -> None:
        """Start the daemon, then warm it with every request of the
        window under another value permutation (the same sizes and
        structure, so the same compiled shapes), one at a time."""
        from jepsen_tpu import serve
        m = self.mix
        rate = float(self.run.rate or m["rate"])
        self.daemon = serve.Daemon(port=0, host="127.0.0.1",
                                   **m.get("daemon", {})).start()
        self.url = f"http://127.0.0.1:{self.daemon.port}"
        self.tmp = tempfile.mkdtemp(prefix="bench-serve-")
        reqs = self._requests(max(1, round(rate * self.run.seconds)))
        self.reqs = [(t, self._body(i, h), h)
                     for i, (t, h, _bad) in enumerate(reqs)]
        self.bad = [b for _t, _h, b in reqs]
        perms = _perm_order(self.rng, self.cfg["values"])
        for i, (_t, _b, h) in enumerate(self.reqs):
            body = self._body(i, self.fam.relabel(
                h, self.fam.perm_of(perms[i % len(perms)],
                                    self.cfg["values"])))
            self._wait([{"id": _post(self.url, body)}],
                       float(m["drain_s"]))

    def _send(self, reqs, t0: float) -> list:
        path = os.path.join(self.tmp, "schedule.json")
        with open(path, "w") as f:
            json.dump({"url": self.url, "t0": t0,
                       "requests": [[t, b] for t, b, _ in reqs]}, f)
        p = subprocess.run([sys.executable,
                            os.path.join(HERE, "loadgen.py"), path],
                           capture_output=True, text=True, check=True)
        self.late_line = p.stderr.strip().splitlines()[-1:]
        return [json.loads(ln) for ln in p.stdout.splitlines()]

    def _wait(self, recs: list, drain_s: float) -> None:
        deadline = time.monotonic() + drain_s
        for r in recs:
            req = self.daemon.registry.get(r["id"]) if r["id"] else None
            while (req is not None and not req.terminal
                   and time.monotonic() < deadline):
                req.done_event.wait(0.05)

    def window(self, seconds: float, annotate) -> None:
        t0 = time.monotonic() + 0.2
        with annotate("bench.send"):
            self.sent = self._send(self.reqs, t0)
        self.window_s = seconds

    def after_window(self) -> None:
        """Wait for every request of the window (at most ``drain_s``
        past its close) and read each verdict's stamps."""
        sent = self.sent
        self._wait(sent, float(self.mix["drain_s"]))
        for r, (_t, _b, h) in zip(sent, self.reqs):
            req = (self.daemon.registry.get(r["id"])
                   if r["status"] == 202 and r["id"] else None)
            res = (req.result or {}) if req is not None else {}
            done = (req is not None and req.status == "done"
                    and req.t_done is not None)
            fell = any(t.get("event") in ("fallback", "swallowed")
                       for t in (req.trace if req is not None else []))
            failed = (not done or not sut.on_device(res)
                      or "error" in res or fell)
            self.records.append({
                "due": r["due"], "sent": r["sent"], "status": r["status"],
                "histories": 1, "ops": h.n_ops,
                "t_done": req.t_done if done else None,
                "queued_s": (req.t_coalesce - req.t_submit)
                if done and req.t_coalesce is not None else None,
                "answer": sut.answer(res), "failed": int(failed),
                "engine": res.get("engine")})

    def backlog(self) -> dict:
        """Median latency of the first and of the last quarter of the
        window's requests (a queue that grows shows as the second far
        above the first), the verdicts per second, and the tail (the
        95th percentile by nearest rank, for the record: too few
        requests in a window to hold it to a bound)."""
        lat = [r["t_done"] - r["due"] for r in self.records
               if r["t_done"] is not None]
        q = max(1, len(lat) // 4)
        done = sum(1 for r in self.records if r["t_done"] is not None)
        p95 = sorted(lat)[max(0, math.ceil(0.95 * len(lat)) - 1)] \
            if lat else None
        return {"early_p50_s": float(np.median(lat[:q])) if lat else None,
                "late_p50_s": float(np.median(lat[-q:])) if lat else None,
                "verdicts_per_s": done / self.window_s,
                "verdict_p95_s": p95}

    def checks(self) -> List[tuple]:
        idx = [i for i, r in enumerate(self.records) if r["t_done"]]
        bad = [i for i in idx if self.bad[i]]
        longest = max(idx, key=lambda i: self.reqs[i][2].n_ops,
                      default=None)
        rest = [i for i in idx if i not in bad and i != longest]
        k = max(0, int(self.mix["sample"]) - len(bad) - 1)
        pick = set(bad) | ({longest} if longest is not None else set())
        pick |= set(self.rng.choice(rest, min(k, len(rest)),
                                    replace=False).tolist())
        return [(self.reqs[i][2], self.records[i]["answer"])
                for i in sorted(pick)]

    def stop(self) -> None:
        self.daemon.shutdown(drain_timeout=30.0)
        for name in os.listdir(self.tmp):
            os.remove(os.path.join(self.tmp, name))
        os.rmdir(self.tmp)


DRIVERS = {"check_packed": CheckPacked, "check_many": CheckMany,
           "serve": Serve}


def compare(fam, pairs: List[tuple],
            control: bool = False) -> Dict[str, int]:
    """The numbers that decide ``correct``: how many sampled histories
    the program answered with another verdict than the reference (the
    family's ``check``), and how many invalid ones with another failing
    op. ``control`` runs the reference with its failing-op guarantee
    broken in the program's place (``skip_failing_op``)."""
    verdict = op = 0
    for h, (valid, key) in pairs:
        if control:
            r = fam.check(h, skip_failing_op=True)
            valid, key = r["valid"], r["op"]
        ref = fam.check(h)
        if valid is not ref["valid"]:
            verdict += 1
        elif ref["valid"] is False and key != ref["op"]:
            op += 1
    return {"verdict_mismatch": verdict, "failing_op_mismatch": op}
