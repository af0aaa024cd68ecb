"""Device-mesh parallelism for the checker searches.

No upstream analogue: the reference's analysis is single-JVM
(``knossos.competition`` merely races two algorithms on two threads —
SURVEY.md §2.4). Here the scaling axes are native to the hardware:

- **key axis** — per-key sub-histories (``jepsen.independent`` semantics)
  are independent searches: shard the batch over the mesh, one vmapped walk
  per device, no communication until the final validity reduction.
- **chunk axis** — a single long history splits into event chunks whose
  boolean transfer matrices are computed in parallel (basis-batched walks)
  and composed; the composition is associative, so chunks shard cleanly
  and combine with an all-gather of small D×D matrices over ICI.

Collectives ride XLA (``psum`` for validity reductions, ``all_gather`` for
matrix combination); there is no NCCL/MPI-style backend to port — the mesh
IS the communication layer.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def devices(platform: Optional[str] = None) -> list:
    import jax
    return jax.devices(platform)


def mesh(axis: str = "shard", devs: Optional[Sequence] = None):
    """A 1-D mesh over ``devs`` (default: all devices)."""
    import jax
    from jax.sharding import Mesh
    devs = list(devs) if devs is not None else jax.devices()
    return Mesh(np.array(devs), (axis,))


def shard_map(fn, mesh, in_specs, out_specs,
              check: Optional[bool] = None):
    """``jax.shard_map`` — one call site for every sharded engine.
    ``check=None`` keeps the library default; False skips the
    varying-axes check (``check_vma``)."""
    import jax
    kw = {} if check is None else {"check_vma": check}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def device_order(devs: Optional[Sequence] = None,
                 axis: str = "shard") -> list:
    """Canonical device placement order for block-sharded lanes: the
    ravel order of the 1-D :func:`mesh` over ``devs`` — the same order
    a ``NamedSharding(mesh, P(axis))`` assigns leading-axis blocks, so
    per-device dispatches (the mesh lockstep lane's lane blocks) and
    NamedSharding placements (the keyed mesh lanes) put block k on the
    same device."""
    return list(mesh(axis, devs).devices.ravel())


def shard_leading_axis(arrays, devs: Optional[Sequence] = None):
    """Place each array with its leading axis sharded across ``devs``
    (padding to a multiple of the device count is the caller's job)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    m = mesh("shard", devs)
    s = NamedSharding(m, P("shard"))
    return [jax.device_put(a, s) for a in arrays]


def chunked_transfer(args, devs: Sequence):
    """Compute per-chunk transfer matrices with the chunk axis sharded over
    ``devs`` via ``shard_map``. ``args`` = (P_mats, xor_cols, bitmask,
    ret_slot_c, slot_ops_c, basis_c) as built by
    :func:`jepsen_tpu.checkers.reach.check_chunked`; the transition
    matrices and static index maps are replicated, the chunked return
    streams and basis blocks are chunk-sharded. Returns a host ndarray
    [n_chunks, D, D]."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jepsen_tpu.checkers import reach

    P_mats, xor_cols, bitmask, ret_slot_c, slot_ops_c, basis_c = args
    n_chunks = ret_slot_c.shape[0]
    n_dev = len(devs)
    if n_chunks % n_dev:
        raise ValueError(f"n_chunks {n_chunks} not divisible by "
                         f"{n_dev} devices")
    m = mesh("chunks", devs)

    def local(P_mats, xor_cols, bitmask, ret_slot_c, slot_ops_c, basis_c):
        inner = jax.vmap(reach._walk_returns_scan,
                         in_axes=(None, None, None, None, None, 0))
        outer = jax.vmap(inner, in_axes=(None, None, None, 0, 0, 0))
        return outer(P_mats, xor_cols, bitmask, ret_slot_c, slot_ops_c,
                     basis_c)

    # replicated operands mix invariant/variant axes inside control
    # flow; skip the varying-axes check
    sm = shard_map(
        local, m,
        in_specs=(P(), P(), P(), P("chunks"), P("chunks"), P("chunks")),
        out_specs=P("chunks"), check=False)
    R = jax.jit(sm)(P_mats, xor_cols, bitmask, ret_slot_c, slot_ops_c,
                    basis_c)
    # [n_chunks, B, S, M] -> [n_chunks, B, D]; B is the (possibly
    # reachability-restricted) basis row count, D = S·M. The fetch
    # goes through reach._fetch: in a multi-process run the sharded
    # result spans non-addressable devices and needs process_allgather
    return reach._fetch(R).reshape(R.shape[0], R.shape[1], -1)
