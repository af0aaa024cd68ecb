"""The default history family: fault-free CAS-register histories, every
op completing ``ok`` or ``fail`` (:mod:`benchmark.gen`), checked by the
program's ``cas-register`` model (:mod:`benchmark.sut`) and by the plain
reference (:mod:`benchmark.reference`).

A family is one file under ``benchmark/families/`` that the drivers
load by the name a configuration gives under ``"family"`` (this one
when it gives none). It holds everything the harness knows about one
kind of history, as these module-level names:

- ``history(rng, cfg, n_ops)``: one valid history from the seed's
  generator; ``corrupt_at(rng, h, frac)``: ``h`` made invalid near
  ``frac`` of its length (anywhere, drawn from ``rng``, when None);
- ``perm_of(index, values)``, ``relabel(h, perm)``: the history under
  a value permutation, another history with the same verdict;
- ``to_ops(h)``: Jepsen op maps in event order, as a client sends them
  (an op that never returns ends ``info``); ``model_name``: the model
  a served request names;
- ``packer(h)``: an object whose ``.packed(perm)`` is the program's
  ``PackedHistory`` (an op that never returns goes in with
  ``crashed=True`` and ``ret_ev = inf_ev``);
- ``check_packed(packed)``, ``check_many(packed_list, devices, diag)``:
  the program's entry points with the family's model;
- ``work(h)``: the roofline count (:mod:`benchmark.work`), an op that
  never returns holding its slot to the end;
- ``check(h, skip_failing_op=False)``, ``op_key``: the family's plain
  reference, which imports nothing of the program, and the key a
  failing op is compared by.
"""
from __future__ import annotations

import numpy as np

from benchmark import gen, reference, sut
from benchmark.work import of_history as work

model_name = "cas-register"
perm_of = gen.perm_of
relabel = gen.relabel
to_ops = gen.to_ops
packer = sut.Packed
check = reference.check
op_key = reference.op_key


def history(rng, cfg: dict, n_ops: int) -> "gen.History":
    return gen.cas_history(rng, n_ops, cfg["processes"], cfg["values"])


def corrupt_at(rng, h: "gen.History", frac) -> "gen.History":
    """Corrupt the successful read nearest ``frac`` of the way through
    the history (a fixed place, so that every seed does the same work
    before the failure), or a random one when ``frac`` is None."""
    if frac is None:
        return gen.corrupt(rng, h)
    reads = np.flatnonzero((h.f == gen.READ) & h.ok)
    i = int(reads[np.argmin(np.abs(reads - frac * h.n_ops))])
    a = h.a.copy()
    a[i] = gen.BAD
    return gen.History(h.proc, h.f, a, h.b, h.ok, h.inv, h.ret)


# looked up in ``sut`` at each call, so that a test's stub of the
# program's entry points reaches every family
def check_packed(packed) -> dict:
    return sut.check_packed(packed)


def check_many(packed_list, devices=None, diag=None) -> list:
    return sut.check_many(packed_list, devices=devices, diag=diag)
