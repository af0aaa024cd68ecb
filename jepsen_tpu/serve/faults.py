"""Test-only fault injection for the serve stack — the self-nemesis.

Jepsen points a nemesis at the system under test; this module points
one at *ourselves*. Named fault points are threaded through the
serving hot path (and one engine-side hook), each a no-op until armed:

- ``tick``        — top of every dispatch iteration (never raises;
                    the trigger clock for scheduled faults).
- ``dispatch``    — entry of every engine attempt, BOTH the device
                    and the host route (a poison request crashes the
                    checker wherever it runs).
- ``device``      — entry of the device route only (a device-path
                    outage: the circuit breaker's food).
- ``prep``        — inside the streaming prep thread
                    (``reach._stage_lockstep``'s producer;
                    env-gated so the engine never imports this module
                    on a clean run).
- ``persist``     — entry of the store persistence write.
- ``journal-write`` — inside ``Journal._write``: instead of raising,
                    the armed write lands a syntactically-valid but
                    garbage-shaped entry and reports success (the
                    bad-payload corruption adversary; replay must
                    quarantine it).
- ``lease-write`` — inside ``Journal.claim``: the armed claim writes
                    a bad-payload lease (junk expiry) the claimer
                    believes it holds; every reader must detect it,
                    quarantine the file, and treat the entry as
                    unclaimed.
- ``clock-jump``  — not a call site: an armed clock jump fires at its
                    scheduled ``tick`` and skews the deadline clock
                    (:func:`clock_skew`, consulted by
                    ``CheckRequest.expired``) so queued/dispatched
                    deadlines expire as if the wall clock leapt.

Arming is programmatic (:func:`arm`, tests) or via the environment
(:func:`arm_from_env`, chaos harness daemons)::

    JEPSEN_TPU_SERVE_FAULTS="dispatch@3;device@2x6;persist@1;
                             clock-jump@4:3600;poison=tenant-x"

Grammar (entries joined by ``;``):

- ``point@N``      fire on the Nth invocation of ``point`` (1-based).
- ``point@NxK``    fire on invocations N..N+K-1 (K consecutive).
- ``clock-jump@N:S``  at the Nth ``tick``, skew the deadline clock
  forward by S seconds (permanently — a jump, not a drift).
- ``poison=T``     raise at every ``dispatch`` whose group contains
  tenant T (models one malformed request that crashes any engine;
  the group-bisect retry must isolate and quarantine it).

Every fault that actually fires bumps ``serve.fault.<name>`` and
appends a ``serve-fault/injected`` decision to the obs ledger — the
chaos harness's "no silent fault" invariant cross-checks those
records against its schedule. Deterministic by construction: firing
depends only on invocation counts, never on wall time or randomness.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional, Sequence


class InjectedFault(RuntimeError):
    """Raised at an armed fault point (self-nemesis, test-only)."""


_lock = threading.RLock()
_armed: List[Dict[str, Any]] = []
_invocations: Dict[str, int] = {}
_skew_s: float = 0.0
_env_loaded = False

_ENV_VAR = "JEPSEN_TPU_SERVE_FAULTS"


def _counter_name(name: str) -> str:
    return "serve.fault." + name.replace("-", "_")


def arm(point: str, *, at: int = 1, times: int = 1,
        skew_s: Optional[float] = None,
        tenant: Optional[str] = None, name: Optional[str] = None
        ) -> None:
    """Arm one fault. ``point`` is the listening call site; ``at`` /
    ``times`` the invocation window; ``tenant`` restricts a
    ``dispatch`` fault to groups containing that tenant (and makes it
    fire on EVERY matching invocation); ``skew_s`` turns the entry
    into a clock jump applied at its ``tick`` instead of a raise."""
    with _lock:
        _armed.append({
            "point": point, "at": int(at), "times": int(times),
            "skew_s": skew_s, "tenant": tenant, "fired": 0,
            "name": name or point,
        })


def reset() -> None:
    """Disarm everything and clear the clock skew (tests)."""
    global _skew_s, _env_loaded
    with _lock:
        _armed.clear()
        _invocations.clear()
        _skew_s = 0.0
        _env_loaded = True      # an explicit reset also pins the env


def enabled() -> bool:
    return bool(_armed) or bool(os.environ.get(_ENV_VAR))


def clock_skew() -> float:
    """Seconds the deadline clock is currently jumped forward by."""
    return _skew_s


def arm_from_env(force: bool = False) -> int:
    """Parse ``JEPSEN_TPU_SERVE_FAULTS`` once (idempotent unless
    ``force``); returns how many entries were armed."""
    global _env_loaded
    with _lock:
        if _env_loaded and not force:
            return 0
        _env_loaded = True
        spec = os.environ.get(_ENV_VAR, "").strip()
        if not spec:
            return 0
        n = 0
        for raw in spec.split(";"):
            raw = raw.strip()
            if not raw:
                continue
            if raw.startswith("poison="):
                arm("dispatch", tenant=raw[len("poison="):],
                    times=1 << 30, name="poison")
                n += 1
                continue
            point, _, when = raw.partition("@")
            point = point.strip()
            arg = None
            if ":" in when:
                when, _, args = when.partition(":")
                arg = float(args)
            times = 1
            if "x" in when:
                when, _, ks = when.partition("x")
                times = int(ks)
            at = int(when or 1)
            if point == "clock-jump":
                arm("tick", at=at, times=times,
                    skew_s=arg if arg is not None else 3600.0,
                    name="clock_jump")
            else:
                arm(point, at=at, times=times, name=point)
            n += 1
        return n


def fire(point: str, tenants: Optional[Sequence[str]] = None) -> None:
    """Invoke a fault point. Raises :class:`InjectedFault` when an
    armed raising fault matches; applies clock skew for due jump
    entries; no-op otherwise. Cheap when nothing is armed."""
    global _skew_s
    if not _env_loaded:
        arm_from_env()
    if not _armed:
        return
    with _lock:
        inv = _invocations.get(point, 0) + 1
        _invocations[point] = inv
        due: Optional[Dict[str, Any]] = None
        for f in _armed:
            if f["point"] != point:
                continue
            if f["tenant"] is not None:
                if not tenants or f["tenant"] not in tenants:
                    continue
                if f["fired"] >= f["times"]:
                    continue
            elif not (f["at"] <= inv < f["at"] + f["times"]):
                continue
            f["fired"] += 1
            due = f
            break
        if due is None:
            return
        name = due["name"]
        skew = due["skew_s"]
        if skew is not None:
            _skew_s += float(skew)
    _record(name, point, inv, tenants)
    if skew is None:
        raise InjectedFault(
            f"injected fault {name!r} at {point} invocation {inv}")


def _record(name: str, point: str, inv: int,
            tenants: Optional[Sequence[str]]) -> None:
    from jepsen_tpu import obs
    obs.count(_counter_name(name))
    obs.decision("serve-fault", "injected", cause=name, point=point,
                 invocation=inv,
                 tenants=sorted(set(tenants or ())) or None)


def fired_counts() -> Dict[str, int]:
    """name -> times fired (for harness-side bookkeeping)."""
    with _lock:
        out: Dict[str, int] = {}
        for f in _armed:
            if f["fired"]:
                out[f["name"]] = out.get(f["name"], 0) + f["fired"]
        return out
