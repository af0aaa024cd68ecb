"""Multi-host distribution — the scale-out story (SURVEY.md §2.4, §5).

The reference has no data-plane communication backend at all: its
analysis is single-JVM, and its only cross-machine traffic is the SSH
control plane (``jepsen.control``). The TPU-native equivalents:

- **control plane** — unchanged in spirit: :mod:`jepsen_tpu.control`
  drives DB nodes over SSH.
- **data plane** — single-controller JAX inside one host;
  ``jax.distributed`` + a hybrid ICI×DCN mesh across hosts. Collectives
  are XLA's (``psum`` liveness reductions, ``all_gather`` of transfer
  matrices); shardings are laid out so the hot axes (keys, chunks) ride
  ICI within a slice and only the final scalar reductions cross DCN.

Usage on each host of a multi-host TPU slice::

    from jepsen_tpu.parallel import distributed
    distributed.initialize()            # env-driven on TPU pods
    mesh = distributed.hybrid_mesh(("dcn", "keys"))
    results = reach.check_many(model, packs, devices=mesh.devices.ravel())

Everything here degrades gracefully to single-process: ``initialize``
is a no-op when no coordinator is configured, and ``hybrid_mesh`` of a
single host is an ordinary 1-slice mesh.
"""
from __future__ import annotations

import os
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

_initialized = False

# wall-clock deadline on cross-host collectives (the gather is the
# ONLY blocking dependency one process has on its peers; past this it
# is treated as a dead peer and the caller's exact-rescue engages)
_TIMEOUT_ENV = "JEPSEN_TPU_DIST_TIMEOUT_S"


def gather_timeout_s() -> float:
    try:
        return float(os.environ.get(_TIMEOUT_ENV, "") or 120.0)
    except ValueError:
        return 120.0


class DistGatherError(RuntimeError):
    """A cross-host gather failed or timed out (dead peer, torn
    coordinator) — callers fall back to local re-derivation."""


# pod driver mode: the multi-controller runtime wants every process to
# run the same program, but a pod DAEMON is single-controller — only
# rank 0 holds the HTTP socket and the work. Driver mode bridges the
# two: rank 0 ships each multi-host walk's operands to the compute
# peers over the work channel below, so every rank enters the same
# walk and the gather collective rendezvouses. Off (the default) for
# SPMD callers — tests and dryruns where every rank already runs the
# same code.
_DRIVER = False
_DRIVER_LOCK = threading.RLock()


def set_driver(on: bool) -> None:
    global _DRIVER
    _DRIVER = bool(on)


def driver_mode() -> bool:
    return _DRIVER


def driver_lock() -> threading.RLock:
    """Held by rank 0 across ship-operands + gather of one walk:
    collectives are matched by issue order, so two concurrent checks
    interleaving theirs would cross-wire every rank."""
    return _DRIVER_LOCK


def _bcast(arr: np.ndarray, timeout_s: Optional[float] = None
           ) -> np.ndarray:
    """``broadcast_one_to_all`` with an optional wall-clock deadline
    (same abandon-the-stuck-thread pattern as :meth:`ChunkShard.gather`
    — a dead peer must cost bounded wall clock, never a hang)."""
    box: dict = {}

    def run() -> None:
        try:
            from jax.experimental import multihost_utils
            box["out"] = np.asarray(
                multihost_utils.broadcast_one_to_all(arr))
        except BaseException as e:                  # noqa: BLE001
            box["err"] = e

    if timeout_s is None:
        run()
    else:
        t = threading.Thread(target=run, daemon=True,
                             name="jepsen-dist-bcast")
        t.start()
        t.join(timeout_s)
    if "out" in box:
        return box["out"]
    if "err" in box:
        raise DistGatherError(
            f"broadcast failed: {box['err']!r}") from box["err"]
    raise DistGatherError(f"broadcast timed out after {timeout_s}s")


def send_work(item: dict, timeout_s: Optional[float] = None) -> None:
    """Rank 0: ship one work item (a dict of numpy arrays / scalars /
    short strings) to every compute peer blocked in :func:`recv_work`.
    Two broadcasts — payload length, then the npz bytes — because
    every rank must present same-shaped operands to a collective.
    Raises :class:`DistGatherError` on a torn pod."""
    import io

    buf = io.BytesIO()
    np.savez(buf, **item)
    data = np.frombuffer(buf.getvalue(), np.uint8)
    _bcast(np.array([data.size], np.int64), timeout_s)
    _bcast(data, timeout_s)


def recv_work() -> dict:
    """Ranks > 0: block until rank 0 ships the next work item (the
    compute-peer loop's sole wait state)."""
    import io

    n = int(_bcast(np.zeros(1, np.int64))[0])
    # the broadcast may hand the bytes back in a widened compute dtype
    # (its reduction path upcasts on some backends) — values are exact,
    # so coerce back to the uint8 wire before reparsing the npz
    data = _bcast(np.zeros(n, np.uint8)).astype(np.uint8)
    with np.load(io.BytesIO(data.tobytes()),
                 allow_pickle=False) as z:
        return {k: z[k] for k in z.files}


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Bring up ``jax.distributed`` for multi-host runs.

    On TPU pods all three arguments are discovered from the environment
    (the standard JAX bootstrap); pass them explicitly for CPU/GPU
    fleets. Returns True if a distributed runtime is (now) active,
    False when running single-process (no coordinator configured) —
    callers need no branching, every mesh helper below works either
    way."""
    global _initialized
    if _initialized:
        return True
    workers = [w for w in
               os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",") if w]
    if (coordinator_address is None and num_processes is None
            and "JAX_COORDINATOR_ADDRESS" not in os.environ
            and "MEGASCALE_COORDINATOR_ADDRESS" not in os.environ
            and len(workers) < 2):      # one hostname = single host
        return False                    # single-process: nothing to do
    import jax
    if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        # CPU fleets (tests, the dist-smoke CI job) need an explicit
        # collectives backend; gloo ships with jaxlib. Must be set
        # before the first backend spins up.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    except (ValueError, RuntimeError):
        # auto-detection came up empty (or already initialized by the
        # launcher) — stay single-process rather than crash the check
        return False
    _initialized = True
    return True


def process_info() -> Tuple[int, int]:
    """(process_index, process_count) — (0, 1) when single-process."""
    import jax
    return jax.process_index(), jax.process_count()


def hybrid_mesh(axis_names: Tuple[str, str] = ("dcn", "ici"),
                devices: Optional[Sequence] = None):
    """A 2-D mesh [hosts(DCN) × per-host devices(ICI)].

    The outer axis crosses host boundaries (DCN-speed collectives —
    keep it for scalar reductions and rare rebalances); the inner axis
    stays within a slice (ICI-speed — shard the hot batch axes here).
    Falls back to a 1×N mesh in single-host runs, so shardings written
    against these axis names work unchanged everywhere."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices) if devices is not None else jax.devices()
    n_proc = max(jax.process_count(), 1)
    per_host = len(devs) // n_proc
    if n_proc > 1 and per_host * n_proc == len(devs):
        try:
            from jax.experimental import mesh_utils
            arr = mesh_utils.create_hybrid_device_mesh(
                (per_host,), (n_proc,), devices=devs)
            return Mesh(arr.reshape(n_proc, per_host), axis_names)
        except Exception:                               # noqa: BLE001
            pass                        # topology discovery unavailable
        # no physical topology (e.g. the CPU backend in multi-process
        # tests): group rows by owning process — that IS the host
        # boundary the outer axis models, so collectives along the
        # inner axis stay process-local wherever the runtime allows
        by_proc = sorted(devs, key=lambda d: (d.process_index, d.id))
        if (len({d.process_index for d in devs}) == n_proc
                and all(d.process_index
                        == by_proc[(i // per_host) * per_host]
                        .process_index
                        for i, d in enumerate(by_proc))):
            return Mesh(np.array(by_proc).reshape(n_proc, per_host),
                        axis_names)
    return Mesh(np.array(devs).reshape(1, len(devs)), axis_names)


class ChunkShard:
    """This process's contiguous slice of a sharded chunk axis — the
    placement contract of the multi-host chunk-lockstep path
    (:func:`reach_chunklock.walk_chunklock`): phase-B walks run
    process-local on ``chunk_range``, and :meth:`gather` is the ONE
    DCN crossing (word-packed summaries, ``all_gather`` along the
    outer axis of :func:`hybrid_mesh`)."""

    __slots__ = ("process_index", "process_count")

    def __init__(self, process_index: int, process_count: int):
        self.process_index = int(process_index)
        self.process_count = int(process_count)

    @classmethod
    def detect(cls) -> Optional["ChunkShard"]:
        """A shard for the live ``jax.distributed`` runtime, or None
        single-process (callers need no branching)."""
        idx, n = process_info()
        return cls(idx, n) if n > 1 else None

    def chunk_range(self, C: int) -> Tuple[int, int]:
        """Contiguous ``[lo, hi)`` of ``C`` chunks owned by this
        process (balanced; trailing processes may own fewer or none)."""
        per = -(-C // self.process_count)
        lo = min(self.process_index * per, C)
        return lo, min(lo + per, C)

    def gather(self, local: np.ndarray) -> np.ndarray:
        """``all_gather`` of one same-shaped array per process along
        the process axis: returns ``[process_count, *local.shape]``
        (ordered by process index). Runs the collective on a worker
        thread under :func:`gather_timeout_s` — a dead peer must cost
        bounded wall clock, not a hang — raising
        :class:`DistGatherError` on failure or deadline (the stuck
        collective thread is abandoned; it is daemonic and the caller
        proceeds with local re-derivation)."""
        box: dict = {}

        def run() -> None:
            try:
                from jax.experimental import multihost_utils
                box["out"] = np.asarray(
                    multihost_utils.process_allgather(local))
            except BaseException as e:              # noqa: BLE001
                box["err"] = e

        t = threading.Thread(target=run, daemon=True,
                             name="jepsen-dist-gather")
        t.start()
        t.join(gather_timeout_s())
        if "out" in box:
            return box["out"]
        if "err" in box:
            raise DistGatherError(
                f"all_gather failed: {box['err']!r}") from box["err"]
        raise DistGatherError(
            f"all_gather timed out after {gather_timeout_s()}s")


def keys_sharding(mesh, batch_axis: str = "ici"):
    """NamedSharding placing a leading key/chunk axis on the ICI axis of
    a :func:`hybrid_mesh` (replicated across DCN)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(batch_axis))
