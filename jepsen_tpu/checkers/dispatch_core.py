"""Shared lockstep dispatch/collect core (ROADMAP item 1 down-payment).

The keyed lockstep lane (:func:`reach._stage_lockstep`) and the
chunk-lockstep engine (:func:`reach_chunklock.walk_chunklock`) once
each grew their own copy of the same pack→dispatch→fallback→recovery
state machine. This module is that seam extracted ONCE, so engine
variants — including the multi-host chunk-sharded path — parameterize
it instead of adding another choreography:

- :class:`DispatchState` — round-robin device placement over the mesh,
  pad-lane dedup accounting, the in-flight window and FIFO drain
  (previously ``reach._LockstepDispatchState``; reach keeps an alias).
- :func:`dispatch_packed` — the bit-packed 0/1 seed upload with the
  dense retry and the exactly-one-fallback record (previously inlined
  in ``walk_chunklock`` phase A; the multi-host phase-A dispatch is the
  second caller).
- :func:`rescue_once` — host-side exact recovery under the ordinary
  contract: the ONE ``engine.fallback`` record lands only AFTER the
  recovery succeeds, so a failure that persists through recovery
  propagates unrecorded (it was not the degraded path's fault).
"""
from __future__ import annotations

import os
import time as _time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from jepsen_tpu import obs
from jepsen_tpu.checkers import transfer

# in-flight lockstep dispatch groups beyond the one being collected.
# Depth 1 queues the NEXT group's device programs — paying its
# marshalling, compile (on a fresh geometry), and transfer host time —
# while the device walks the current group; the same K-deep dispatch
# trick bench.py's kernel probe validates. Deeper pipelines pin more
# operand sets in HBM for ~no added overlap (the host stage is the
# bottleneck, and it is already fully hidden at depth 1).
PIPE_DEPTH = 1

# default in-flight window for the SERVE lanes (groups staged per lane,
# including the one being collected): deep enough to hide host
# pack+fetch behind device walks across whole admission groups, shallow
# enough that at most K operand sets are pinned per lane. The autotune
# table can override per geometry bucket (kind "pipeline").
SERVE_PIPE_K = 4


def pipeline_enabled() -> bool:
    """The stage/collect dispatch pipeline gate.
    ``JEPSEN_TPU_NO_PIPELINE=1`` forces K=1 everywhere — every group
    is collected before the next is staged, the bit-identical
    degenerate mode (consulted per call: tests toggle it)."""
    return not os.environ.get("JEPSEN_TPU_NO_PIPELINE")


def pipeline_k(geom_key: Optional[str] = None, *,
               default: int = SERVE_PIPE_K) -> int:
    """Resolve the in-flight window K (groups staged per lane,
    including the one being collected). Precedence: the
    ``JEPSEN_TPU_NO_PIPELINE=1`` opt-out (K=1), the
    ``JEPSEN_TPU_PIPE_K=<n>`` override, a measured autotune winner
    for this geometry bucket (kind ``pipeline``, recorded by
    ``tools/ablate_lane.py --pipeline``; staleness-guarded like every
    other entry), else ``default``. Always >= 1."""
    if not pipeline_enabled():
        return 1
    env = os.environ.get("JEPSEN_TPU_PIPE_K")
    if env:
        try:
            return max(1, int(env))
        # jtlint: ok fallback — a malformed override reads as the default depth
        except ValueError:
            pass
    if geom_key:
        from jepsen_tpu.checkers import autotune
        w = autotune.winner("pipeline", geom_key)
        if w is not None:
            try:
                return max(1, int(w))
            # jtlint: ok fallback — a malformed table entry reads as the default depth
            except (TypeError, ValueError):
                pass
    return max(1, int(default))


def poll_ready(x) -> bool:
    """True when a dispatched device value's result is resident (its
    fetch would not block). Conservative: anything without an
    ``is_ready`` probe — numpy results, degenerate staged handles —
    reads as ready, so readiness polling can only make a collect
    eager, never skip one.  The probe itself lives with the rest of
    the wire knowledge in :func:`transfer.device_ready`."""
    return transfer.device_ready(x)


def inflight_ready(fl) -> bool:
    """Readiness of one dispatched-but-unfetched lockstep group
    (:class:`reach_batch.BatchInflight`): the word body's queued
    results, or the dense body's final carried config set."""
    out = getattr(fl, "word_out", None)
    if out is not None:
        return all(poll_ready(o) for o in out)
    final = getattr(fl, "final", None)
    return poll_ready(final) if final is not None else True


class DispatchState:
    """Per-dispatch bookkeeping of the keyed lockstep lane
    (:func:`reach._stage_lockstep`): round-robin device placement over
    the mesh, pad-lane dedup accounting (mesh pad lanes are
    cross-group duplicates — their returns must not count as real
    work), the in-flight window, and the FIFO drain. The lane places
    and admits each group as its prep arrives, drains to ``depth`` as
    it goes, and its staged handle drains the rest on collect."""

    __slots__ = ("devs", "n_dev", "depth", "dead", "seen", "dev_groups",
                 "inflight", "inflight_hwm", "fetch_s",
                 "fetch_degraded")

    def __init__(self, devices: Optional[Sequence], dead: np.ndarray,
                 k: Optional[int] = None):
        self.devs = list(devices) if devices else None
        self.n_dev = len(self.devs) if self.devs else 1
        # K groups in flight per device lane (K includes the one being
        # collected, so the drain limit is n_dev*K - 1); the default
        # K = PIPE_DEPTH+1 is the historical one-walking-plus-one-
        # queued window, and JEPSEN_TPU_NO_PIPELINE=1 collapses to the
        # collect-after-every-dispatch degenerate mode
        if k is None:
            k = pipeline_k(default=PIPE_DEPTH + 1)
        self.depth = self.n_dev * max(1, int(k)) - 1
        self.dead = dead
        self.seen: set = set()
        self.dev_groups = [0] * self.n_dev
        self.inflight: list = []
        self.inflight_hwm = 0
        self.fetch_s = 0.0
        self.fetch_degraded = False

    def place(self, gi: int, g, prep) -> Tuple[int, Dict[str, Any]]:
        """Pin group ``gi`` to its round-robin device; returns the
        device index and the dispatch span args (with the group's mask
        width ``W``, kernel lanes ``H`` and config rows ``M``)."""
        di = gi % self.n_dev
        sp: Dict[str, Any] = {"lanes": len(g)}
        geom = getattr(prep, "geom", None)
        if geom is not None:
            _B, W, M, _S, H, _O1, _R_pad = geom
            sp.update(W=W, H=H, M=M)
        if self.devs:
            prep.device = self.devs[di]
            self.dev_groups[di] += 1
            sp["device"] = di
        return di, sp

    def admit(self, g, fl, di: int) -> dict:
        """Group diag (with pad-lane dedup) + in-flight append."""
        from jepsen_tpu.checkers import reach_batch

        gd = reach_batch.group_diag(fl.geom, fl.R_lens,
                                    getattr(fl, "key_W", None))
        x = fl.dsegs.get("xfer")
        if x is not None:
            # wire bytes this group actually moved vs the blanket
            # int32/f32 format — summed by _lockstep_accounting
            gd["put_bytes"], gd["put_bytes_unpacked"] = x
        if self.devs:
            gd["device"] = di
            dup = sum(int(fl.R_lens[j]) for j, k in enumerate(g)
                      if k in self.seen)
            self.seen.update(g)
            if dup:
                gd["pad_lane_returns"] = dup
        self.inflight.append((g, fl, di))
        self.inflight_hwm = max(self.inflight_hwm, len(self.inflight))
        obs.count("pipeline.staged")
        return gd

    def collect_ready(self, limit: int = 0) -> None:
        """Readiness-polled collect: FIFO-fetch only groups whose
        device results are already resident, stopping at the first
        still-walking group (never past ``limit`` remaining). A lane
        thread calls this between stages so finished predecessors
        drain without blocking the next stage."""
        from jepsen_tpu.checkers import reach_batch  # noqa: F401

        while (len(self.inflight) > limit
               and inflight_ready(self.inflight[0][1])):
            self.drain(len(self.inflight) - 1)

    def drain(self, limit: int) -> None:
        """FIFO-fetch verdicts until at most ``limit`` groups remain in
        flight (0 = drain all)."""
        from jepsen_tpu.checkers import reach_batch

        while len(self.inflight) > limit:
            g0, fl0, di0 = self.inflight.pop(0)
            t0 = _time.monotonic()
            sp: Dict[str, Any] = {"lanes": len(g0)}
            if self.devs:
                sp["device"] = di0
            with obs.span("lockstep.collect", **sp):
                self.dead[np.asarray(g0, np.int64)] = \
                    reach_batch.collect_returns_batch(fl0)
            if getattr(fl0, "degraded", False):
                self.fetch_degraded = True
            self.fetch_s += _time.monotonic() - t0

    def mesh_info(self, pad_lanes: int) -> Optional[dict]:
        if not self.devs:
            return None
        return {"n_devices": self.n_dev,
                "per_device_groups": self.dev_groups,
                "inflight_max": self.inflight_hwm,
                "pad_lanes": pad_lanes}


def dispatch_packed(run, dense_args: Sequence[np.ndarray],
                    seed: np.ndarray, base_bytes: int, *,
                    stage: str = "packed-xfer"):
    """Dispatch ``run(*dense_args, seed_wire)`` with the exactly-0/1
    ``seed`` operand bit-packed on the wire (8 per byte, unpacked on
    device) when the transfer diet allows. A packed dispatch failure
    retries ONCE with the dense seed and records exactly one
    ``engine.fallback`` — AFTER the dense retry succeeds, because a
    failure that persists dense (e.g. Pallas unsupported on this
    backend) was not the packed wire's fault and must propagate
    unrecorded. ``base_bytes`` is the blanket int32/f32 wire baseline
    for the put accounting."""
    import jax.numpy as jnp

    dense_bytes = sum(int(a.nbytes) for a in dense_args)
    if transfer.packed_enabled():
        seed_w = transfer.pack_bool(seed)
        transfer.count_put(dense_bytes + seed_w.nbytes, base_bytes)
        try:
            return run(*dense_args, seed_w)
        except Exception as e:                          # noqa: BLE001
            # the dense retry re-crosses the whole operand set
            transfer.count_put(dense_bytes + seed.nbytes, 0)
            out = run(*dense_args, jnp.asarray(seed))
            obs.engine_fallback(stage, type(e).__name__)
            return out
    transfer.count_put(dense_bytes + seed.nbytes, base_bytes)
    return run(*dense_args, jnp.asarray(seed))


def rescue_once(stage: str, cause: str, fn, **fields):
    """Run host-side exact recovery ``fn()`` under the exactly-one-
    fallback contract: the single ``engine.fallback(stage, cause)``
    record lands only once ``fn`` has succeeded. Shared by the
    multi-host gather rescue and any future engine variant's recovery
    ladder, so the contract is written (and tested) once."""
    out = fn()
    obs.engine_fallback(stage, cause, **fields)
    return out
