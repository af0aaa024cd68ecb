"""The wire protocol: stdlib ``ThreadingHTTPServer``, no dependencies
(matching ``web.py``'s style). Three routes:

- ``POST /check`` — submit a history. JSON body::

      {"model": "cas-register",            # models.<name> constructor
       "history": [{"process":0,"type":"invoke","f":"read"}, ...],
       "tenant": "team-a",                 # or X-Tenant header
       "timeout-s": 30.0,                  # optional deadline
       "idempotency-key": "job-17",        # optional dedup key:
                                           # duplicate POSTs return
                                           # the ORIGINAL id (the
                                           # window survives restarts
                                           # via the journal)
       "options": {"max_states": 100000}}  # engine kw (allow-listed)

  ``Content-Type: application/edn`` parses the SAME shape from EDN
  (an upstream Jepsen ``history.edn`` pasted as the ``:history``
  value works). Replies ``202 {"id": ..., "status": "queued"}``,
  ``400`` on malformed input, ``429`` + ``Retry-After`` under
  backpressure.
- ``GET /check/<id>`` — status/result. ``result`` carries the full
  checker verdict (witness included) once ``status`` is terminal,
  plus the stage ``waterfall`` (admit→coalesce→walk→publish), the
  stitched dispatcher ``trace``, and the request's attributed
  ``device-s``. A quarantined request (the isolated poison member of
  a crashed dispatch group) answers a structured **500**. Verdicts
  published just before a crash answer from the journal's completion
  marker after restart. ``DELETE /check/<id>`` cancels a queued
  request (journal-only entries get their cancelled marker, so a
  restart cannot resurrect them).
- ``GET /stats`` — queue depths, per-tenant ledger counts, cache
  counters, per-geometry dispatch counts, latency-histogram digests,
  breaker/journal state, and the rolling time-series ring.
  ``GET /healthz`` — liveness + degradation (breaker state, journal
  backlog).
- ``GET /metrics`` — Prometheus text exposition (every counter,
  numeric gauge, and latency histogram with ``_bucket``/``_sum``/
  ``_count`` series; scrape-ready).
- ``POST /profile`` — ``{"dispatches": N}`` arms ``jax.profiler``
  around the next N dispatches; the capture persists under
  ``<store-root>/serve/profile-<ts>/``.
- ``POST /session`` — open a streaming check session (long-lived
  check, device-resident carried frontier);
  ``POST /session/<id>/append`` ships one event block and returns
  the incremental verdict + tail-alarm status synchronously (202 +
  request id past ``wait-s``); ``POST /session/<id>/close``
  resolves the tail and returns the exact final verdict + witness
  (differential-identical to the one-shot chain);
  ``GET /session/<id>`` is the status view. Opens and appends are
  journaled before their acknowledgement, so sessions ride a
  SIGKILL: replay re-derives the frontier under the original id.

Fleet mode (``replica_id=``): N daemons share ONE journal root.
Every admitted request and open session carries a lease (replica id
+ wall-clock expiry) in the journal; a replica only dispatches work
it holds the lease on, so the same entry is never double-dispatched.
A background scan (every ``lease_ttl_s / 3``) renews the replica's
own leases and adopts work whose holder stopped renewing — a
SIGKILL'd replica's claims expire and drain through the survivors.
Any replica answers ``GET /check/<id>`` (done markers live in the
shared journal); duplicate POSTs dedup across replicas through the
shared idempotency index. Sessions are PINNED to their claiming
replica (the carried frontier is device state): an append landing on
the wrong replica answers 409 with the pin while the lease is live,
and adopts the session by journal replay once it expires.
"""
from __future__ import annotations

import json
import logging
import threading
import time
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional, Tuple

import numpy as np

from jepsen_tpu import edn
from jepsen_tpu import history as h
from jepsen_tpu import obs
from jepsen_tpu.op import Op
from jepsen_tpu.serve import faults, recovery
from jepsen_tpu.serve import journal as jr
from jepsen_tpu.serve import request as rq
from jepsen_tpu.serve import session as sn
from jepsen_tpu.serve.coalesce import AdmissionQueue, Backpressure
from jepsen_tpu.serve.engine import Dispatcher

log = logging.getLogger("jepsen.serve")

# engine options a client may set per request — bounded to the knobs
# that cannot destabilize co-tenants (no devices=, no interpret=)
_CLIENT_OPTS = ("max_states", "max_slots", "max_dense", "time_limit",
                "max_dense_txns", "consistency")


def _filter_opts(raw: Any, strict: bool = True) -> Dict[str, Any]:
    """Allow-list client options and canonicalize ``"consistency"``
    to the sorted tuple-of-levels form (so every spelling of the same
    level set coalesces into the same signature). ``strict`` raises
    on an unknown level (the admission path's 400); the replay paths
    pass False — an invalid value cannot have been admitted, so it is
    dropped rather than wedging the replay loop."""
    opts = {k: v for k, v in (raw or {}).items() if k in _CLIENT_OPTS}
    if "consistency" in opts:
        from jepsen_tpu.txn import lattice
        try:
            opts["consistency"] = list(
                lattice.canon_levels(opts["consistency"]))
        except ValueError:
            if strict:
                raise
            obs.decision("serve-opts", "drop", cause="bad-consistency")
            del opts["consistency"]
    return opts

_MODEL_NAMES = ("register", "cas-register", "mutex", "multi-register",
                "set-model", "fifo-queue", "unordered-queue",
                "noop-model", "txn-list-append")


def resolve_model(name: str):
    """Model name -> fresh model instance (the CLI's vocabulary:
    ``cas-register`` -> ``models.cas_register()``). The transactional
    marker ``txn-list-append`` routes its dispatch groups through
    ``facade.auto_check_txn`` instead of the linearizable engines —
    and, because the model type is part of the coalescing signature,
    txn requests coalesce into their own groups by construction."""
    if name == "txn-list-append":
        from jepsen_tpu.txn import ops as txn_ops
        return txn_ops.list_append_model()
    from jepsen_tpu import models
    if name not in _MODEL_NAMES:
        raise ValueError(f"unknown model {name!r}; "
                         f"have {list(_MODEL_NAMES)}")
    return getattr(models, name.replace("-", "_"))()


def parse_check_body(body: bytes, content_type: str,
                     default_tenant: str = "anonymous"
                     ) -> Tuple[str, str, list, Dict[str, Any],
                                Optional[float], Optional[str]]:
    """Decode a POST /check body -> (tenant, model_name, ops,
    options, timeout_s, idempotency_key). Raises ValueError on
    malformed input."""
    text = body.decode("utf-8")
    if "edn" in (content_type or ""):
        vals = edn.loads_all(text)
        if len(vals) != 1:
            raise ValueError("expected one EDN map")
        data = edn.to_plain(vals[0])
    else:
        data = json.loads(text)
    if not isinstance(data, dict):
        raise ValueError("body must be a map")
    raw_hist = data.get("history")
    if not isinstance(raw_hist, list) or not raw_hist:
        raise ValueError("'history' must be a non-empty list of ops")
    ops = [Op.from_dict(edn.to_plain(d) if not isinstance(d, dict)
                        else d) for d in raw_hist]
    if ops and ops[0].index < 0:
        ops = h.index(ops)
    model_name = str(data.get("model", "cas-register"))
    # tenant names are client-controlled and key bounded per-tenant
    # state: cap the length here, cardinality in the registry
    tenant = str(data.get("tenant") or default_tenant)[:64]
    options = _filter_opts(data.get("options"))
    timeout_s = data.get("timeout-s", data.get("timeout_s"))
    if timeout_s is not None:
        timeout_s = float(timeout_s)
        if timeout_s <= 0:
            raise ValueError("'timeout-s' must be positive")
    # client-supplied idempotency key: duplicate POSTs with the same
    # key dedup to the original request id (bounded-length, like
    # tenant names — it keys bounded daemon state)
    idem = data.get("idempotency-key", data.get("idempotency_key"))
    idem = str(idem)[:128] if idem is not None else None
    return tenant, model_name, ops, options, timeout_s, idem


class _Server(ThreadingHTTPServer):
    """The stdlib threading server with a listen backlog sized for
    burst arrivals: the default 5 drops (RST) concurrent connects the
    accept loop has not reached yet, which a thousand-session open
    wave hits immediately. The backlog is pending CONNECTS only —
    admission backpressure still bounds accepted work."""
    request_queue_size = 128


class Daemon:
    """Everything the serving layer owns: registry, admission queue,
    dispatcher thread, HTTP server. ``start()`` returns after the
    socket is listening; ``shutdown()`` is graceful — stops admitting,
    drains in-flight work, then stops the dispatcher.

    Binds LOOPBACK by default: unlike the read-only results browser,
    this endpoint accepts work (unauthenticated compute + store
    writes) — exposing it (``host="0.0.0.0"``) is a deliberate act."""

    def __init__(self, *, port: int = 8642, host: str = "127.0.0.1",
                 queue_depth: int = 256,
                 max_inflight_per_tenant: int = 8,
                 group: int = 32,
                 engine_kw: Optional[Dict[str, Any]] = None,
                 store_root: Optional[str] = None,
                 persist: bool = False,
                 max_body_bytes: int = 32 << 20,
                 journal: bool = True,
                 journal_keep_terminal: int = 256,
                 retry_policy: Optional[recovery.RetryPolicy] = None,
                 breaker: Optional[recovery.CircuitBreaker] = None,
                 dispatch_deadline_s: Optional[float] = None,
                 session_tenant_cap: int = 64,
                 session_idle_ttl_s: Optional[float] = 3600.0,
                 lanes: int = 1,
                 replica_id: Optional[str] = None,
                 lease_ttl_s: float = 10.0) -> None:
        # the queue bounds request COUNT; this bounds request BYTES —
        # both are needed for "backpressure, never OOM": worst-case
        # queued history memory is queue_depth * max_body_bytes-ish
        self.max_body_bytes = int(max_body_bytes)
        # self-nemesis faults arm from the environment here so a
        # chaos-harness daemon subprocess carries its fault schedule
        faults.arm_from_env()
        self.registry = rq.Registry()
        self.queue = AdmissionQueue(
            max_depth=queue_depth,
            max_inflight_per_tenant=max_inflight_per_tenant,
            group=group, lanes=lanes)
        # durable admission journal (WAL): admitted requests are
        # journaled before their 202 and replayed on restart — only
        # with a store root (durability needs somewhere durable)
        self.journal: Optional[jr.Journal] = None
        if journal and store_root is not None:
            from jepsen_tpu import store
            self.journal = jr.Journal(
                store.serve_journal_dir(store_root),
                keep_terminal=journal_keep_terminal)
        # fleet mode: several replicas over one journal root, work
        # partitioned by per-entry lease. A replica id without a
        # journal would be a fleet with no shared state to fleet over.
        self.replica_id = str(replica_id) if replica_id else None
        self.lease_ttl_s = float(lease_ttl_s)
        self.fleet = (self.replica_id is not None
                      and self.journal is not None)
        self._fleet_stop = threading.Event()
        self._fleet_thread: Optional[threading.Thread] = None
        # pod mode: a multi-host (jax.distributed) daemon is ONE fleet
        # replica — rank 0 owns the lease and the HTTP socket; ranks
        # > 0 are compute peers (run_compute_peer, never a Daemon).
        # process_info degrades to (0, 1) single-process, so this is
        # dormant off-pod.
        try:
            from jepsen_tpu.parallel import distributed
            self.rank, self.n_ranks = distributed.process_info()
        # jtlint: ok fallback — capability probe: no jax on the protocol-only path, single-process roles
        except Exception:                               # noqa: BLE001
            self.rank, self.n_ranks = 0, 1
        if self.n_ranks > 1:
            obs.gauge("dist.processes", self.n_ranks)
            obs.gauge("dist.rank", self.rank)
            if self.journal is not None:
                # the lease payload carries the pod shape: a sibling
                # replica inspecting the lease sees it fronts n ranks
                self.journal.lease_meta = {"ranks": self.n_ranks}
        # (tenant, idempotency key) -> request id (bounded; seeded
        # from the journal so the dedup window survives restarts;
        # tenant-scoped so one tenant's key cannot map onto — or leak
        # the status of — another tenant's request)
        self._idem_lock = threading.Lock()
        self._idem: "OrderedDict[Any, str]" = OrderedDict()
        # ids whose admission is IN FLIGHT on some HTTP worker thread:
        # a concurrent duplicate that hits the index before the winner
        # finishes journaling must dedup to the winner, not race past
        # it (check-then-act would admit both)
        self._admitting: set = set()
        if self.journal is not None:
            self._idem.update(self.journal.idempotency_index())
        # the coalescer's group width rides into the engine-side
        # re-plan (facade filters it to check_many's `group=`): both
        # planners must agree on the dispatch width or the admission
        # bucketing would be re-split downstream
        ekw = {"group": group}
        ekw.update(engine_kw or {})
        self.dispatcher = Dispatcher(self.queue, self.registry,
                                     engine_kw=ekw,
                                     store_root=store_root,
                                     persist=persist,
                                     retry_policy=retry_policy,
                                     breaker=breaker,
                                     dispatch_deadline_s=
                                     dispatch_deadline_s,
                                     journal=self.journal,
                                     lanes=lanes)
        if self.journal is not None:
            # every terminal transition — dispatcher publish, queued
            # timeout, cancel — marks the WAL entry complete, so a
            # restart never resurrects finished (or cancelled) work
            # (and, in fleet mode, frees the lease for the verdict's
            # entry — the done marker now answers for it everywhere)
            jnl = self.journal

            def _on_terminal(req: "rq.CheckRequest") -> None:
                jnl.finish(req.id, req.status, req.result)
                if self.fleet:
                    jnl.release(req.id, self.replica_id)

            self.registry.on_terminal = _on_terminal
        # streaming check sessions: long-lived checks whose carried
        # frontier the dispatcher advances per append block. Bounded
        # three ways: globally (max_open), per tenant (one tenant
        # must not exhaust the global bound), and in time (an open
        # session idle past the TTL is force-closed by the sweeper —
        # an abandoned session pins device state forever otherwise)
        self.sessions = sn.SessionRegistry(
            tenant_max_open=session_tenant_cap,
            idle_ttl_s=session_idle_ttl_s)
        self.dispatcher.sessions = self.sessions
        self._sweeper: Optional[threading.Thread] = None
        self._sweeper_stop = threading.Event()
        handler = type("Handler", (_Handler,), {"daemon_ref": self})
        self.httpd = _Server((host, port), handler)
        self._serve_thread: Optional[threading.Thread] = None
        self.accepting = True

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self, *, dispatch: bool = True) -> "Daemon":
        """``dispatch=False`` starts only the HTTP side — protocol
        tests exercise admission/backpressure without a device
        engine behind the queue."""
        from jepsen_tpu import envcheck
        envcheck.check_once()       # typo'd opt-outs warn, not no-op
        if dispatch:
            self.dispatcher.start()
            self.replay_journal()
            self.replay_sessions()
            self._start_sweeper()
            self._start_fleet_scan()
            self._pod_up()
        self._serve_thread = threading.Thread(
            target=self.httpd.serve_forever, name="serve-http",
            daemon=True)
        self._serve_thread.start()
        return self

    def serve_forever(self) -> None:
        """Foreground mode (the CLI): blocks until interrupted, then
        shuts down gracefully."""
        self.dispatcher.start()
        self.replay_journal()
        self.replay_sessions()
        self._start_sweeper()
        self._start_fleet_scan()
        self._pod_up()
        try:
            self.httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            self.shutdown()

    def _pod_up(self) -> None:
        """Rank 0 of a pod: turn on driver mode (multi-host walks ship
        their operands to the compute peers) and run the warmup ping —
        one tiny word payload through the work channel and the DCN
        gather, proving every peer answers collectives BEFORE real
        checks ride on them. A failed warmup turns driver mode back
        off: the daemon serves single-host rather than paying a gather
        timeout per check against a torn pod."""
        if self.n_ranks <= 1 or self.rank != 0:
            return
        from jepsen_tpu.parallel import distributed
        distributed.set_driver(True)
        ping = np.arange(32, dtype=np.uint32).reshape(1, 32)
        try:
            with distributed.driver_lock():
                distributed.send_work(
                    {"op": "gather-ping", "words": ping},
                    timeout_s=distributed.gather_timeout_s())
                out = distributed.ChunkShard.detect().gather(ping)
            if out.shape[0] != self.n_ranks:
                raise RuntimeError(
                    f"warmup gathered {out.shape[0]}/{self.n_ranks}")
            obs.count("dist.warmup_ok")
            log.info("pod warmup: %d ranks answered", self.n_ranks)
        except Exception as e:                          # noqa: BLE001
            distributed.set_driver(False)
            obs.count("dist.warmup_failed")
            log.warning("pod warmup failed (%r): serving single-host",
                        e)

    def shutdown(self, drain_timeout: float = 30.0) -> bool:
        self.accepting = False
        if self.n_ranks > 1 and self.rank == 0:
            # release the compute peers (best-effort: a torn pod's
            # peers die by signal instead)
            from jepsen_tpu.parallel import distributed
            if distributed.driver_mode():
                try:
                    with distributed.driver_lock():
                        distributed.send_work({"op": "shutdown"},
                                              timeout_s=10.0)
                # jtlint: ok fallback — best-effort peer release on shutdown; peers also die by signal
                except Exception:                       # noqa: BLE001
                    pass
                distributed.set_driver(False)
        self._sweeper_stop.set()
        self._fleet_stop.set()
        if self._fleet_thread is not None:
            self._fleet_thread.join(5.0)
        drained = self.dispatcher.drain(timeout=drain_timeout)
        self.dispatcher.stop()
        if self._serve_thread is not None:
            # BaseServer.shutdown() handshakes with the serve loop; on
            # a daemon whose HTTP side never started (replay-only
            # tests, failed startups) it would wait forever
            self.httpd.shutdown()
        self.httpd.server_close()
        if self._serve_thread is not None:
            self._serve_thread.join(5.0)
        self.dispatcher._write_stats_file()
        return drained

    # -- journal replay --------------------------------------------------
    def replay_journal(self) -> int:
        """Feed unfinished journal entries back through the admission
        queue under their ORIGINAL ids (restart recovery). Deadlines
        re-derive from the wall clock: a deadline that passed while
        the daemon was dead replays as an immediate timeout. A corrupt
        entry is quarantined (marked terminal with a structured
        error), never looped on. Returns how many entries replayed."""
        if self.journal is None:
            return 0
        n = 0
        for rid in self.journal.pending_ids():
            if self.registry.get(rid) is not None:
                # already live HERE (double replay call / fleet-scan
                # revisit): in fleet mode, renew the lease so sibling
                # scans keep seeing a live holder
                if self.fleet:
                    self.journal.claim(rid, replica=self.replica_id,
                                       ttl_s=self.lease_ttl_s)
                continue
            if self.fleet and not self.journal.claim(
                    rid, replica=self.replica_id,
                    ttl_s=self.lease_ttl_s):
                continue    # a sibling's live lease: its work, not ours
            entry = self.journal.load_entry(rid)
            try:
                if entry is None:
                    raise ValueError("unreadable journal entry")
                ops = jr.history_from_edn(entry["history-edn"])
                if not ops:
                    raise ValueError("empty journaled history")
                if ops[0].index < 0:
                    ops = h.index(ops)
                model = resolve_model(str(entry["model"]))
                packed = h.pack(ops)
            except Exception as e:                      # noqa: BLE001
                log.warning("journal entry %s unreplayable: %s",
                            rid, e)
                obs.engine_fallback("serve-journal",
                                    type(e).__name__, id=rid,
                                    replay=True)
                self.journal.finish(
                    rid, rq.QUARANTINED,
                    {"valid": "unknown", "quarantined": True,
                     "cause": "journal-corrupt",
                     "error": f"{type(e).__name__}: {e}"})
                continue
            deadline = None
            timeout_s = entry.get("timeout-s")
            if timeout_s:
                elapsed = time.time() - float(
                    entry.get("submitted-at") or time.time())
                deadline = time.monotonic() \
                    + max(0.0, float(timeout_s) - elapsed)
            opts = _filter_opts(entry.get("options"), strict=False)
            req = rq.CheckRequest(
                id=rid, tenant=str(entry.get("tenant") or "anonymous"),
                model_name=str(entry["model"]), model=model,
                packed=packed, history=ops, n_ops=int(packed.n),
                opts=opts, deadline=deadline,
                idem_key=entry.get("idempotency-key"),
                journaled=True)
            self.registry.add(req)
            # force past the depth bound: this work was ALREADY
            # admitted (its 202 is in a client's hands)
            self.queue.submit(req, force=True)
            self.registry.ledger_record(req.tenant, "replayed",
                                        id=rid, ops=int(packed.n))
            obs.count("serve.journal.replayed")
            # (the dedup index already carries this entry's key:
            # __init__ seeds it from journal.idempotency_index())
            n += 1
        if n:
            log.info("journal replay: %d request(s) readmitted", n)
        return n

    def replay_sessions(self) -> int:
        """Re-create every open (unclosed) journaled session and
        replay its append blocks in seq order THROUGH THE ENGINE —
        the carried frontier re-derives deterministically from the
        stream, so a session rides a SIGKILL keeping its id, its seq
        counter, and its verdict. Corrupt session metadata gets a
        structured close marker (quarantine analog), never a loop."""
        if self.journal is None:
            return 0
        n = 0
        for sid in self.journal.open_session_ids():
            if self.sessions.get(sid) is not None:
                # live here: renew the pin so siblings 409 appends to
                # this session instead of adopting it out from under
                # its device-resident frontier
                if self.fleet:
                    self.journal.claim(sid, replica=self.replica_id,
                                       ttl_s=self.lease_ttl_s)
                continue
            if self.fleet and not self.journal.claim(
                    sid, replica=self.replica_id,
                    ttl_s=self.lease_ttl_s):
                continue    # pinned to a live sibling
            if self._replay_one_session(sid):
                n += 1
        if n:
            log.info("session replay: %d session(s) re-derived", n)
        return n

    def _replay_one_session(self, sid: str) -> bool:
        """Rebuild ONE journaled session through the engine (boot
        replay and fleet adoption share this path — a session always
        re-derives from its durable stream, never from copied state).
        Returns whether a live session came out of it."""
        meta = self.journal.load_session(sid)
        try:
            if meta is None:
                raise ValueError("unreadable session entry")
            model_name = str(meta["model"])
            model = resolve_model(model_name)
            opts = _filter_opts(meta.get("options"), strict=False)
        except Exception as e:                          # noqa: BLE001
            log.warning("session %s unreplayable: %s", sid, e)
            obs.engine_fallback("serve-journal",
                                type(e).__name__, session=sid,
                                replay=True)
            self.journal.session_close_marker(
                sid, {"valid": "unknown",
                      "cause": "session-journal-corrupt",
                      "error": f"{type(e).__name__}: {e}"})
            return False
        sess = sn.Session(
            sid, str(meta.get("tenant") or "anonymous"),
            model_name, model, opts)
        blocks = self.journal.session_appends(sid)
        for seq, entry in blocks:
            if seq != sess.seq + 1:
                # a seq GAP (missing/unreadable block file):
                # replay TRUNCATES here — advancing past the hole
                # would derive a frontier from a stream missing a
                # block AND falsely dedup the client's retry of
                # it. The client's retries re-apply from the
                # truncation point.
                obs.engine_fallback("serve-journal", "SeqGap",
                                    session=sid, seq=seq,
                                    expected=sess.seq + 1)
                break
            try:
                ops = jr.history_from_edn(entry["history-edn"])
                sess.advance_block(ops, seq=seq)
            except Exception as e:                      # noqa: BLE001
                # a torn block was never acknowledged: stop HERE
                # (same truncation argument — sess.seq must not
                # move past an unapplied block)
                obs.engine_fallback("serve-journal",
                                    type(e).__name__, session=sid,
                                    seq=seq)
                break
            sess.seq = seq
            sess.replayed += 1
        # the replayed stream counts as activity: a session must
        # not be swept as idle the instant its daemon restarts
        sess.last_active_mono = time.monotonic()
        try:
            self.sessions.add(sess)
        except RuntimeError as e:
            # past the open-session bound: leave the session
            # journaled (a later restart, after closes/GC, can
            # still replay it) — a full registry must degrade a
            # session, never abort the daemon's boot
            log.warning("session %s not replayed: %s", sid, e)
            obs.engine_fallback("serve-journal", "SessionBound",
                                session=sid, replay=True)
            return False
        self.registry.ledger_record(sess.tenant,
                                    "session-replayed",
                                    session=sid,
                                    appends=len(blocks))
        obs.count("serve.session.replayed")
        return True

    # -- idle-session sweeper --------------------------------------------
    def _start_sweeper(self) -> None:
        """Background idle-TTL sweep: an abandoned open session pins
        its carried device state (frontier buffer / closure masks)
        and a tenant-cap slot forever; the sweeper force-closes
        sessions idle past the TTL through the ordinary close path
        (exact verdict, journal close marker — a replayed daemon will
        not resurrect them)."""
        ttl = self.sessions.idle_ttl_s
        if not ttl or self._sweeper is not None:
            return
        interval = max(1.0, min(30.0, float(ttl) / 4.0))

        def _sweep_loop() -> None:
            while not self._sweeper_stop.wait(interval):
                try:
                    self.expire_idle_sessions()
                # jtlint: ok fallback — sweep failures retry next tick; evictions are counted
                except Exception:                       # noqa: BLE001
                    log.exception("idle-session sweep failed")

        self._sweeper = threading.Thread(
            target=_sweep_loop, name="serve-session-sweeper",
            daemon=True)
        self._sweeper.start()

    def expire_idle_sessions(self) -> int:
        """Force-close open sessions idle past the registry TTL
        (``serve.session.evicted_idle`` per eviction). Returns how
        many closes were initiated."""
        ttl = self.sessions.idle_ttl_s
        if not ttl:
            return 0
        n = 0
        for sess in self.sessions.idle_open(float(ttl)):
            idle_s = round(time.monotonic() - sess.last_active_mono, 3)
            obs.count("serve.session.evicted_idle")
            self.registry.ledger_record(
                sess.tenant, "session-evicted-idle",
                session=sess.id, idle_s=idle_s)
            log.info("session %s idle %.1fs > ttl %.1fs: force-close",
                     sess.id, idle_s, ttl)
            code, payload = self.session_close(sess.id)
            if code in (200, 202):
                n += 1
        return n

    # -- fleet scan (renew own leases, adopt expired ones) ---------------
    def _start_fleet_scan(self) -> None:
        """Background lease maintenance, fleet mode only. Every
        ``lease_ttl_s / 3`` (a renew cadence that survives two missed
        ticks before the lease lapses) the replica re-runs the replay
        paths: for work it already holds that is a lease RENEWAL; for
        pending entries whose holder stopped renewing — a SIGKILL'd
        sibling — the claim STEALS the expired lease and the entry
        replays here. That single mechanism is both heartbeat and
        failover: no separate membership protocol."""
        if not self.fleet or self._fleet_thread is not None:
            return
        interval = max(0.2, self.lease_ttl_s / 3.0)

        def _scan_loop() -> None:
            while not self._fleet_stop.wait(interval):
                try:
                    self.fleet_scan()
                # jtlint: ok fallback — a failed scan retries next tick; leases it missed renewing are re-claimable, never lost
                except Exception:                       # noqa: BLE001
                    log.exception("fleet scan failed")

        self._fleet_thread = threading.Thread(
            target=_scan_loop, name="serve-fleet-scan", daemon=True)
        self._fleet_thread.start()

    def fleet_scan(self) -> Tuple[int, int]:
        """One renew-and-adopt pass (exposed for tests: deterministic
        lease handoff without waiting on the scan thread). Returns
        (requests adopted, sessions adopted)."""
        return self.replay_journal(), self.replay_sessions()

    # -- streaming sessions (called from HTTP worker threads) ------------
    def session_open(self, body: bytes, content_type: str,
                     header_tenant: Optional[str]) -> Tuple[int, Dict]:
        if not self.accepting:
            return 503, {"error": "shutting down"}
        try:
            text = body.decode("utf-8") if body else "{}"
            if "edn" in (content_type or ""):
                vals = edn.loads_all(text)
                data = edn.to_plain(vals[0]) if vals else {}
            else:
                data = json.loads(text) if text.strip() else {}
            if not isinstance(data, dict):
                raise ValueError("body must be a map")
            model_name = str(data.get("model", "cas-register"))
            model = resolve_model(model_name)
            tenant = str(data.get("tenant") or header_tenant
                         or "anonymous")[:64]
            options = _filter_opts(data.get("options"))
        except Exception as e:                          # noqa: BLE001
            return 400, {"error": f"{type(e).__name__}: {e}"}
        sid = sn.new_session_id()
        if self.journal is not None:
            try:
                # durable BEFORE the id is returned: the journaled
                # appends need a session entry to replay into
                self.journal.session_open(sid, tenant=tenant,
                                          model_name=model_name,
                                          options=options)
            except OSError as e:
                obs.engine_fallback("serve-journal",
                                    type(e).__name__, session=sid)
                return 500, {"error": f"journal write failed: {e}"}
            if self.fleet:
                # pin the session HERE before the id is returned: a
                # sibling's scan racing this open must see the pin,
                # not adopt a session whose opener is mid-reply
                self.journal.claim(sid, replica=self.replica_id,
                                   ttl_s=self.lease_ttl_s)
        sess = sn.Session(sid, tenant, model_name, model, options)
        try:
            self.sessions.add(sess)
        except sn.TenantSessionCap as e:
            if self.journal is not None:
                self.journal.discard_session(sid)
            return 429, {"error": str(e), "cause": "tenant-cap",
                         "retry-after-s": 1.0}
        except RuntimeError as e:
            if self.journal is not None:
                self.journal.discard_session(sid)
            return 429, {"error": str(e), "retry-after-s": 1.0}
        self.registry.ledger_record(tenant, "session-opened",
                                    session=sid, model=model_name)
        out = {"session": sid, "status": "open",
               "tenant": tenant, "model": model_name,
               "engine": sess.engine_name}
        if self.fleet:
            out["pinned-to"] = self.replica_id
        return 201, out

    def _parse_append(self, body: bytes, content_type: str
                      ) -> Tuple[list, Optional[int], Optional[float],
                                 float]:
        text = body.decode("utf-8")
        if "edn" in (content_type or ""):
            vals = edn.loads_all(text)
            if len(vals) != 1:
                raise ValueError("expected one EDN map")
            data = edn.to_plain(vals[0])
        else:
            data = json.loads(text)
        if not isinstance(data, dict):
            raise ValueError("body must be a map")
        raw = data.get("history")
        if not isinstance(raw, list) or not raw:
            raise ValueError("'history' must be a non-empty list of "
                             "ops")
        ops = [Op.from_dict(edn.to_plain(d) if not isinstance(d, dict)
                            else d) for d in raw]
        seq = data.get("seq")
        seq = int(seq) if seq is not None else None
        timeout_s = data.get("timeout-s", data.get("timeout_s"))
        timeout_s = float(timeout_s) if timeout_s is not None else None
        wait_s = float(data.get("wait-s", 30.0))
        return ops, seq, timeout_s, wait_s

    def _adopt_session(self, sid: str
                       ) -> Tuple[Optional[sn.Session],
                                  Optional[Tuple[int, Dict]]]:
        """Fleet resolution of a session that is NOT live locally
        (and not closed — callers check that first). While the
        claiming replica's lease is live the session is PINNED there:
        the caller answers 409 with the pin, and the client retries
        against it (the carried frontier is that replica's device
        state — adopting a live session would fork it). Once the
        lease expires — the holder died — this replica claims the pin
        and re-derives the frontier from the journaled stream, and
        the append proceeds HERE. Returns (session, None) or
        (None, (code, payload))."""
        if self.fleet \
                and self.journal.load_session(sid) is not None:
            holder = self.journal.lease_live(sid)
            if holder is not None and holder != self.replica_id:
                return None, (409, {
                    "error": f"session {sid!r} is pinned to "
                             f"replica {holder!r}",
                    "session": sid, "pinned-to": holder,
                    "cause": "session-pinned"})
            if self.journal.claim(sid, replica=self.replica_id,
                                  ttl_s=self.lease_ttl_s) \
                    and self._replay_one_session(sid):
                sess = self.sessions.get(sid)
                if sess is not None:
                    obs.count("serve.session.adopted")
                    self.registry.ledger_record(
                        sess.tenant, "session-adopted", session=sid,
                        replica=self.replica_id)
                    log.info("session %s adopted by replica %s",
                             sid, self.replica_id)
                    return sess, None
        return None, (404, {"error": f"unknown session {sid!r}"})

    def session_append(self, sid: str, body: bytes,
                       content_type: str) -> Tuple[int, Dict]:
        if not self.accepting:
            return 503, {"error": "shutting down"}
        sess = self.sessions.get(sid)
        if sess is None:
            term = (self.journal.session_lookup_closed(sid)
                    if self.journal is not None else None)
            if term is not None:
                return 409, {"error": f"session {sid!r} is closed",
                             "session": sid, "status": "closed"}
            sess, err = self._adopt_session(sid)
            if sess is None:
                return err
        try:
            ops, seq, timeout_s, wait_s = self._parse_append(
                body, content_type)
        except Exception as e:                          # noqa: BLE001
            return 400, {"error": f"{type(e).__name__}: {e}"}
        with sess.lock:
            # closed/closing re-checked UNDER the lock: an append
            # racing a concurrent close must get its 409, not journal
            # a block into a closing session
            if sess.closed or sess.closing:
                return 409, {"error": f"session {sid!r} is closed",
                             "session": sid, "status": "closed"}
            if seq is not None and seq <= sess.seq:
                # at-least-once on the client side, exactly-once on
                # the frontier: a retried block (response lost to a
                # crash/restart) dedups to the already-applied seq
                obs.count("serve.session.deduped")
                out = sess.status()
                out.update({"deduped": True, "seq": seq})
                return 200, out
            if seq is not None and seq != sess.seq + 1:
                # a seq GAP is a protocol error, never silently
                # renumbered: accepting block k+2 as k+1 would break
                # the dedup contract (a later retry of the true k+1
                # would then double-advance the frontier)
                return 409, {"error": f"seq gap: expected "
                                      f"{sess.seq + 1}, got {seq}",
                             "session": sid, "seq": sess.seq}
            this_seq = sess.seq + 1
            if self.journal is not None:
                # durable BEFORE the verdict: the replay re-derives
                # the frontier from journaled blocks in seq order
                try:
                    self.journal.session_append_entry(sid, this_seq,
                                                      ops)
                except OSError as e:
                    obs.engine_fallback("serve-journal",
                                        type(e).__name__, session=sid)
                    return 500, {"error":
                                 f"journal write failed: {e}"}
            # NO deadline on an append: a journaled block is part of
            # the session's durable stream — expiring it queued would
            # leave a hole in the carried frontier while seq already
            # advanced past it (the client bounds its own wait with
            # wait-s and polls GET /check/<id> for slow dispatches)
            del timeout_s
            req = rq.CheckRequest(
                id=rq.new_request_id(), tenant=sess.tenant,
                model_name=sess.model_name, model=sess.model,
                packed=None, history=ops, n_ops=len(ops),
                opts=dict(sess.opts),
                kind="session-append", session=sess, seq=this_seq)
            try:
                self.registry.add(req)
                self.queue.submit(req)
            except Backpressure as e:
                self.registry.remove(req.id)
                if self.journal is not None:
                    self.journal.discard_session_append(sid, this_seq)
                self.registry.ledger_record(sess.tenant, "rejected",
                                            cause="backpressure",
                                            session=sid)
                return 429, {"error": str(e), "retry-after-s": 1.0}
            sess.seq = this_seq
        # synchronous by default: the append's whole point is a
        # verdict seconds after the ops ran. A slow dispatch returns
        # 202 + the request id; the verdict arrives via GET /check/<id>
        if req.done_event.wait(wait_s) and req.result is not None:
            out = dict(req.result)
            out["id"] = req.id
            out["status"] = req.status
            return 200, out
        return 202, {"id": req.id, "session": sid, "seq": this_seq,
                     "status": req.status}

    def session_close(self, sid: str, body: bytes = b""
                      ) -> Tuple[int, Dict]:
        sess = self.sessions.get(sid)
        if sess is None:
            term = (self.journal.session_lookup_closed(sid)
                    if self.journal is not None else None)
            if term is not None:
                out = {"session": sid, "status": "closed",
                       "recovered-from-journal": True}
                if term.get("result") is not None:
                    out["result"] = term["result"]
                return 200, out
            sess, err = self._adopt_session(sid)
            if sess is None:
                return err
        if sess.closed:
            return 200, {"session": sid, "status": "closed",
                         "result": dict(sess.result or {})}
        try:
            wait_s = float((json.loads(body.decode() or "{}")
                            or {}).get("wait-s", 120.0)) \
                if body else 120.0
        # jtlint: ok fallback — malformed wait-s defaults; the close itself proceeds
        except Exception:                               # noqa: BLE001
            wait_s = 120.0
        with sess.lock:
            if sess.closing:
                return 409, {"error": f"close of {sid!r} already in "
                                      f"flight"}
            sess.closing = True
            req = rq.CheckRequest(
                id=rq.new_request_id(), tenant=sess.tenant,
                model_name=sess.model_name, model=sess.model,
                packed=None, history=(), n_ops=len(sess.ops),
                opts=dict(sess.opts),
                kind="session-close", session=sess,
                seq=sess.seq + 1)
            try:
                self.registry.add(req)
                self.queue.submit(req)
            except Backpressure as e:
                sess.closing = False
                self.registry.remove(req.id)
                return 429, {"error": str(e), "retry-after-s": 1.0}
        if req.done_event.wait(wait_s) and req.result is not None:
            if not sess.closed:
                # the close dispatch crashed (closing was cleared so
                # a retry can succeed): report the TRUTH — the
                # session is still open — not a fabricated "closed"
                return 500, {"session": sid, "status": "open",
                             "id": req.id,
                             "error": "close failed; retry",
                             "result": dict(req.result)}
            out = {"session": sid, "status": "closed",
                   "id": req.id, "result": dict(req.result)}
            return 200, out
        return 202, {"id": req.id, "session": sid,
                     "status": req.status}

    def session_status(self, sid: str) -> Tuple[int, Dict]:
        sess = self.sessions.get(sid)
        if sess is not None:
            return 200, sess.status()
        term = (self.journal.session_lookup_closed(sid)
                if self.journal is not None else None)
        if term is not None:
            out = {"session": sid, "status": "closed",
                   "recovered-from-journal": True}
            if term.get("result") is not None:
                out["result"] = term["result"]
            return 200, out
        if self.fleet and self.journal.load_session(sid) is not None:
            # a status GET answers from the shared journal without
            # moving the pin (only appends/closes adopt): any replica
            # can tell the client where the session lives
            return 200, {"session": sid, "status": "open",
                         "fleet": True,
                         "pinned-to": self.journal.lease_live(sid)}
        return 404, {"error": f"unknown session {sid!r}"}

    # -- request handling (called from HTTP worker threads) -------------
    def _reserve_idem(self, tenant: str, idem: str,
                      req_id: str) -> Optional[str]:
        """Atomically claim (tenant, key) for ``req_id``. Returns the
        ALREADY-known id on a hit (the caller dedups), None when this
        request now owns the key. The reservation happens before any
        journaling or queue admission, so concurrent duplicate POSTs
        cannot both pass a check-then-act window."""
        with self._idem_lock:
            known = self._idem.get((tenant, idem))
            if known is not None:
                return known
            self._idem[(tenant, idem)] = req_id
            self._admitting.add(req_id)
            while len(self._idem) > 4096:
                self._idem.popitem(last=False)
            return None

    def _settle_idem(self, tenant: str, idem: Optional[str],
                     req_id: str, admitted: bool) -> None:
        """Resolve a reservation: keep the mapping on success, retract
        it (index + in-flight mark) when admission failed."""
        if idem is None:
            return
        with self._idem_lock:
            self._admitting.discard(req_id)
            if not admitted and self._idem.get((tenant, idem)) \
                    == req_id:
                self._idem.pop((tenant, idem), None)

    def _dedup_response(self, tenant: str, idem: str,
                        known: str) -> Optional[Tuple[int, Dict]]:
        """Map a duplicate POST onto the original request: live ones
        report their current status, journaled terminal ones their
        recorded one. Scoped by tenant. A reservation whose admission
        is still in flight on another worker thread is WAITED OUT
        (admission is a journal write + queue insert, milliseconds) —
        returning its id early would hand the client a 202 that
        dangles if the winner's admission then fails."""
        deadline = time.monotonic() + 5.0
        while True:
            # read the in-flight mark BEFORE the tiers: a winner that
            # settles between a tier miss and a later mark check would
            # otherwise look failed, and this duplicate admit fresh
            with self._idem_lock:
                in_flight = known in self._admitting
            req = self.registry.get(known)
            if req is not None:
                obs.count("serve.journal.deduped")
                return 202, {"id": known, "status": req.status,
                             "tenant": req.tenant, "deduped": True}
            term = (self.journal.lookup_terminal(known)
                    if self.journal is not None else None)
            if term is not None:
                obs.count("serve.journal.deduped")
                return 202, {"id": known,
                             "status": term.get("status", "done"),
                             "deduped": True}
            if self.fleet \
                    and self.journal.load_entry(known) is not None:
                # pending on a SIBLING replica (journaled, not
                # terminal, not in this registry): dedup to it — the
                # client polls GET /check/<id>, which any replica
                # answers from the shared journal
                obs.count("serve.journal.deduped")
                return 202, {"id": known, "status": "queued",
                             "deduped": True, "fleet": True,
                             "claimed-by":
                                 self.journal.lease_live(known)}
            if not in_flight:
                # not mid-admission and resolvable on no tier:
                # either the winner's admission failed (its
                # retraction already popped the index) or the
                # entry fell out of retention — admit fresh
                with self._idem_lock:
                    if self._idem.get((tenant, idem)) == known:
                        self._idem.pop((tenant, idem), None)
                return None
            if time.monotonic() >= deadline:
                # pathological stall of the winner: fail THIS
                # duplicate loudly rather than dangle or double-admit
                return 503, {"error": "idempotent admission of "
                             f"{known!r} still in flight"}
            time.sleep(0.002)

    def submit(self, body: bytes, content_type: str,
               header_tenant: Optional[str]) -> Tuple[int, Dict]:
        import time as _time
        if not self.accepting:
            return 503, {"error": "shutting down"}
        try:
            tenant, model_name, ops, options, timeout_s, idem = \
                parse_check_body(body, content_type,
                                 default_tenant=header_tenant
                                 or "anonymous")
            model = resolve_model(model_name)
            packed = h.pack(ops)
            from jepsen_tpu.txn.ops import ListAppend, micro_ops
            if isinstance(model, ListAppend):
                # validate micro-ops AT ADMISSION: a malformed txn
                # must be this client's 400, not a dispatch-time crash
                # that degrades every co-tenant in the coalesced group
                for op in ops:
                    if op.f == "txn":
                        micro_ops(op.value)
        except Exception as e:                          # noqa: BLE001
            return 400, {"error": f"{type(e).__name__}: {e}"}
        req = rq.CheckRequest(
            id=rq.new_request_id(), tenant=tenant,
            model_name=model_name, model=model, packed=packed,
            history=ops, n_ops=int(packed.n), opts=options,
            deadline=(_time.monotonic() + timeout_s
                      if timeout_s else None),
            idem_key=idem)
        if idem is not None:
            known = self._reserve_idem(tenant, idem, req.id)
            if known is None and self.fleet:
                # the local index only knows THIS replica's
                # admissions (plus the boot-time seed): a sibling may
                # already hold the key — rescan the shared journal
                # index before letting this admission through
                sibling = self.journal.idempotency_index().get(
                    (tenant, idem))
                if sibling is not None and sibling != req.id:
                    self._settle_idem(tenant, idem, req.id,
                                      admitted=False)
                    known = sibling
            if known is not None:
                dup = self._dedup_response(tenant, idem, known)
                if dup is not None:
                    return dup
                # the known id was stale on every tier and has been
                # retracted: claim the key for this request
                if self._reserve_idem(tenant, idem, req.id) is not None:
                    # lost the re-claim race to another fresh POST:
                    # let that one win, admit this without a key
                    idem = None
                    req.idem_key = None
        if self.journal is not None:
            # durable BEFORE the 202: a client holding this id holds
            # a claim that survives SIGKILL. Append precedes queue
            # entry so a crash between the two replays the request
            # (at-least-once) instead of losing it.
            try:
                self.journal.append(
                    req_id=req.id, tenant=tenant,
                    model_name=model_name, options=options,
                    timeout_s=timeout_s, idempotency_key=idem,
                    history=ops)
                req.journaled = True
                if self.fleet:
                    # lease the entry to THIS replica before the 202:
                    # a sibling's scan racing the admission must see
                    # a live holder, never adopt-and-double-dispatch
                    # (fresh id — the exclusive-create cannot collide)
                    self.journal.claim(req.id,
                                       replica=self.replica_id,
                                       ttl_s=self.lease_ttl_s)
            except OSError as e:
                obs.engine_fallback("serve-journal",
                                    type(e).__name__, append=True)
                self._settle_idem(tenant, idem, req.id,
                                  admitted=False)
                return 500, {"error": f"journal write failed: {e}"}
        try:
            self.registry.add(req)
            self.queue.submit(req)
        except Backpressure as e:
            # the id was never returned to the client: retract it so
            # rejected requests cannot accumulate in the registry —
            # or resurrect from the journal
            self.registry.remove(req.id)
            if self.journal is not None:
                self.journal.discard(req.id)
            self._settle_idem(tenant, idem, req.id, admitted=False)
            self.registry.ledger_record(tenant, "rejected",
                                        cause="backpressure")
            return 429, {"error": str(e), "retry-after-s": 1.0}
        self._settle_idem(tenant, idem, req.id, admitted=True)
        self.registry.ledger_record(tenant, "admitted", id=req.id,
                                    ops=int(packed.n))
        return 202, {"id": req.id, "status": req.status,
                     "tenant": tenant, "ops": int(packed.n)}

    def lookup(self, req_id: str) -> Tuple[int, Dict]:
        req = self.registry.get(req_id)
        if req is None:
            # a request that completed just before a crash: its
            # registry state died with the process, but the journal's
            # completion marker carries the verdict
            term = (self.journal.lookup_terminal(req_id)
                    if self.journal is not None else None)
            if term is not None:
                out: Dict[str, Any] = {
                    "id": req_id,
                    "status": term.get("status", "done"),
                    "recovered-from-journal": True}
                if term.get("result") is not None:
                    out["result"] = term["result"]
                code = (500 if out["status"] == rq.QUARANTINED
                        else 200)
                return code, out
            if self.fleet \
                    and self.journal.load_entry(req_id) is not None:
                # pending on another replica: answer the poll from
                # the shared journal (status detail lives with the
                # claiming replica; the verdict will land in the
                # shared done marker either way)
                return 200, {"id": req_id, "status": "queued",
                             "fleet": True,
                             "claimed-by":
                                 self.journal.lease_live(req_id)}
            return 404, {"error": f"unknown request {req_id!r}"}
        # a quarantined request is a structured 500: the daemon is
        # healthy, THIS request poisoned its dispatches
        code = 500 if req.status == rq.QUARANTINED else 200
        return code, req.to_json()

    def profile(self, body: bytes) -> Tuple[int, Dict]:
        """Arm on-demand profiling: the next N dispatches run under
        ``jax.profiler.trace``. 409 when already armed or when the
        daemon has no store root to persist the capture into."""
        try:
            data = json.loads(body) if body else {}
            n = int(data.get("dispatches", 1))
            if not 1 <= n <= 1000:
                raise ValueError("dispatches must be in 1..1000")
        except Exception as e:                          # noqa: BLE001
            return 400, {"error": f"{type(e).__name__}: {e}"}
        try:
            d = self.dispatcher.arm_profile(n)
        except RuntimeError as e:
            return 409, {"error": str(e)}
        except Exception as e:                          # noqa: BLE001
            # e.g. an unwritable store root: the capture dir could
            # not be created — an HTTP error, never a dropped socket
            return 500, {"error": f"{type(e).__name__}: {e}"}
        return 202, {"profile-dir": d, "dispatches": n}

    def cancel(self, req_id: str) -> Tuple[int, Dict]:
        req = self.registry.get(req_id)
        if req is None:
            # journaled but not (yet) replayed into the registry — a
            # crash-recovery window: write the cancelled marker so a
            # restart cannot resurrect cancelled work
            if self.journal is not None \
                    and self.journal.cancel_pending(req_id):
                obs.count("serve.cancelled")
                return 200, {"id": req_id, "status": rq.CANCELLED,
                             "cancelled-in-journal": True}
            return 404, {"error": f"unknown request {req_id!r}"}
        queued = self.queue.cancel(req_id)
        if queued is not None:
            obs.count("serve.cancelled")
            obs.count(f"serve.tenant."
                      f"{self.registry.bucket_tenant(req.tenant)}"
                      f".cancelled")
            self.registry.finish(queued, rq.CANCELLED,
                                 {"valid": "unknown",
                                  "cause": "cancelled"})
            self.registry.ledger_record(req.tenant, "cancelled",
                                        id=req_id)
        else:
            # already walking: flag it; the dispatch abort hook and
            # completion path observe the flag
            req.cancel_requested = True
        return 200, req.to_json()

    def stats(self) -> Dict[str, Any]:
        out = self.dispatcher.stats()
        if self.fleet:
            out["fleet"] = {
                "replica": self.replica_id,
                "lease-ttl-s": self.lease_ttl_s,
                "leases": self.journal.stats().get("leases", 0)}
        if self.n_ranks > 1:
            out["dist"] = {"rank": self.rank, "ranks": self.n_ranks}
        return out

    def health(self) -> Dict[str, Any]:
        """Liveness + degradation: ``ok`` means the daemon serves;
        ``degraded`` means it serves from the host path while the
        device-path breaker is open (or probing half-open)."""
        breaker = self.dispatcher.breaker
        out: Dict[str, Any] = {"ok": True,
                               "degraded": breaker.degraded,
                               "breaker": breaker.to_json()}
        if self.journal is not None:
            out["journal"] = {"pending": self.journal.pending_count()}
        if self.fleet:
            out["fleet"] = {"replica": self.replica_id,
                            "lease-ttl-s": self.lease_ttl_s}
        return out


def run_compute_peer(*, rank: int, n_ranks: int) -> None:
    """Pod mode, ranks > 0: no HTTP socket, no lease, no dispatcher —
    the process stays resident to join the multi-host walks rank 0's
    daemon drives. The loop blocks in :func:`distributed.recv_work`;
    each received item is one walk (operands shipped by the driver —
    this rank's phase B joins the gather collective, its verdict is
    discarded, rank 0's fold is the one that serves). Exits on the
    driver's shutdown broadcast. Deliberately NOT a Daemon:
    constructing one here would bind a second HTTP port and claim
    leases rank 0 already owns."""
    from jepsen_tpu.checkers import reach_chunklock as rcl
    from jepsen_tpu.parallel import distributed

    obs.gauge("dist.processes", n_ranks)
    obs.gauge("dist.rank", rank)
    log.info("compute peer up: rank %d of %d", rank, n_ranks)
    print(f'{{"peer": {rank}, "ranks": {n_ranks}}}', flush=True)
    while True:
        item = distributed.recv_work()
        op = str(item.get("op"))
        if op == "shutdown":
            log.info("compute peer rank %d: clean shutdown", rank)
            return
        try:
            if op == "gather-ping":
                # pod warmup: prove this rank answers a DCN collective
                distributed.ChunkShard.detect().gather(
                    np.ascontiguousarray(item["words"]))
            elif op == "chunklock":
                rcl.walk_chunklock(
                    np.ascontiguousarray(item["P"], np.float32),
                    np.ascontiguousarray(item["ret_slot"], np.int8),
                    np.ascontiguousarray(item["slot_ops"]),
                    int(item["M"]), n_chunks=int(item["n_chunks"]),
                    e_pad=int(item["e_pad"]),
                    suffix=int(item["suffix"]),
                    interpret=bool(int(item["interpret"])))
        except Exception:                               # noqa: BLE001
            # a peer-side failure costs rank 0 one gather timeout and
            # a local rescue, never correctness; stay resident
            obs.count("dist.peer_errors")
            log.exception("compute peer rank %d: work item failed",
                          rank)


class _Handler(BaseHTTPRequestHandler):
    daemon_ref: Daemon = None           # type: ignore[assignment]
    protocol_version = "HTTP/1.1"

    def _reply(self, code: int, payload: Dict) -> None:
        body = json.dumps(payload, default=str).encode()
        self._reply_raw(code, body, "application/json")

    def _reply_raw(self, code: int, body: bytes,
                   content_type: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        if code == 429:
            self.send_header("Retry-After", "1")
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self) -> None:                          # noqa: N802
        path = self.path.rstrip("/")
        n = int(self.headers.get("Content-Length") or 0)
        if n > self.daemon_ref.max_body_bytes:
            # refuse BEFORE reading: a body cap enforced after
            # rfile.read would already have paid the memory
            self._reply(413, {"error": f"body {n} bytes exceeds "
                              f"{self.daemon_ref.max_body_bytes}"})
            return
        if path == "/profile":
            body = self.rfile.read(n) if n else b""
            code, payload = self.daemon_ref.profile(body)
            self._reply(code, payload)
            return
        if path == "/session":
            body = self.rfile.read(n) if n else b""
            code, payload = self.daemon_ref.session_open(
                body, self.headers.get("Content-Type", ""),
                self.headers.get("X-Tenant"))
            self._reply(code, payload)
            return
        if path.startswith("/session/"):
            rest = path[len("/session/"):]
            sid, _, action = rest.partition("/")
            body = self.rfile.read(n) if n else b""
            if action == "append":
                code, payload = self.daemon_ref.session_append(
                    sid, body, self.headers.get("Content-Type", ""))
            elif action == "close":
                code, payload = self.daemon_ref.session_close(
                    sid, body)
            else:
                code, payload = 404, {
                    "error": "POST /session/<id>/append or .../close"}
            self._reply(code, payload)
            return
        if path != "/check":
            self._reply(404,
                        {"error": "POST /check, /session or "
                                  "/profile only"})
            return
        body = self.rfile.read(n) if n else b""
        code, payload = self.daemon_ref.submit(
            body, self.headers.get("Content-Type", ""),
            self.headers.get("X-Tenant"))
        self._reply(code, payload)

    def do_GET(self) -> None:                           # noqa: N802
        path = self.path.split("?", 1)[0]
        if path.startswith("/check/"):
            code, payload = self.daemon_ref.lookup(
                path[len("/check/"):].strip("/"))
            self._reply(code, payload)
            return
        if path.startswith("/session/"):
            code, payload = self.daemon_ref.session_status(
                path[len("/session/"):].strip("/"))
            self._reply(code, payload)
            return
        if path.rstrip("/") == "/stats":
            self._reply(200, self.daemon_ref.stats())
            return
        if path.rstrip("/") == "/metrics":
            # Prometheus text exposition of the process-global
            # recorder: counters, numeric gauges, histogram ladders
            from jepsen_tpu import obs
            self._reply_raw(200, obs.prometheus_text().encode(),
                            "text/plain; version=0.0.4; "
                            "charset=utf-8")
            return
        if path.rstrip("/") == "/healthz":
            self._reply(200, self.daemon_ref.health())
            return
        self._reply(404, {"error": f"no route {path!r}"})

    def do_DELETE(self) -> None:                        # noqa: N802
        path = self.path.split("?", 1)[0]
        if path.startswith("/check/"):
            code, payload = self.daemon_ref.cancel(
                path[len("/check/"):].strip("/"))
            self._reply(code, payload)
            return
        self._reply(404, {"error": "DELETE /check/<id> only"})

    def log_message(self, *args) -> None:               # quiet
        pass
