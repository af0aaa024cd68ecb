"""The Jepsen tutorial's partition deployment: CAS registers on
independent keys, 10 client threads a key, under a
``partition-random-halves`` nemesis (``doc/tutorial/06-refining.md``).

A key's history starts as the default family's fault-free CAS-register
history (:func:`gen.cas_history`), thread t bound to node t mod
``nodes``. Its events map linearly onto the key's ``key_s`` of wall
time, placed at a phase of the nemesis cycle drawn from the seed: the
network is whole for ``off_s``, then cut for ``on_s``, a fresh random
``minority`` of the nodes on the small side. A thread bound to a
minority node times out on the first op it invokes while the cut is
up: a read ends ``fail`` (it did not happen), a write or CAS ends
``info`` (it may have happened, at any later time) and stays pending
to the end of the history. Jepsen then replaces the crashed client's
process, so the thread's later ops in the key run under a fresh
process id (``processes + k`` for the key's k-th crash). A key holds
up to one crashed op per minority thread, so at most 4 at 10 threads
over 5 nodes, and at most 14 ops pending at once.

Crashing an op or failing a read only widens what a linearization may
do, so a valid history stays valid, and a read corrupted to a value
nobody writes stays unexplainable. The packer, ``to_ops``, ``work``
and ``check`` below treat crashed ops as the family hook describes
(``benchmark/families/cas.py``).
"""
from __future__ import annotations

import dataclasses
import faulthandler
from dataclasses import dataclass

import numpy as np

from benchmark import gen, reference, sut, work as _work

model_name = "cas-register"
perm_of = gen.perm_of
op_key = reference.op_key


@dataclass(frozen=True)
class History(gen.History):
    """``crashed``: the op never returned (``info``); its ``ret`` is the
    position of its ``info`` completion. A timed-out read has ``ok``
    False, as a failed CAS."""
    crashed: np.ndarray


def _base(h: History) -> gen.History:
    return gen.History(h.proc, h.f, h.a, h.b, h.ok, h.inv, h.ret)


def _with(h: History, g: gen.History) -> History:
    return History(g.proc, g.f, g.a, g.b, g.ok, g.inv, g.ret, h.crashed)


def history(rng, cfg: dict, n_ops: int) -> History:
    g = gen.cas_history(rng, n_ops, cfg["processes"], cfg["values"])
    p = cfg["partition"]
    cycle = float(p["off_s"]) + float(p["on_s"])
    phase = float(rng.uniform(0.0, cycle))
    minority = set(rng.choice(int(p["nodes"]), int(p["minority"]),
                              replace=False).tolist())
    # an op's invocation time within the nemesis cycle
    t = (phase + g.inv * float(p["key_s"]) / (2 * g.n_ops)) % cycle
    cut = t >= float(p["off_s"])
    ok, crashed, proc = g.ok.copy(), np.zeros(g.n_ops, bool), g.proc.copy()
    k = 0
    for thread in range(int(cfg["processes"])):
        if thread % int(p["nodes"]) not in minority:
            continue
        mine = np.flatnonzero(g.proc == thread)       # invocation order
        hit = mine[cut[mine]]
        if not len(hit):
            continue
        i = int(hit[0])
        if g.f[i] == gen.READ:
            ok[i] = False                               # :fail
            continue
        crashed[i] = True                               # :info
        proc[mine[mine > i]] = int(cfg["processes"]) + k
        k += 1
    return History(proc, g.f, g.a, g.b, ok, g.inv, g.ret, crashed)


def corrupt_at(rng, h: History, frac) -> History:
    """The successful read nearest ``frac`` (or one drawn from ``rng``)
    reads a value nobody wrote; a timed-out read is never picked."""
    if frac is None:
        return _with(h, gen.corrupt(rng, _base(h)))
    reads = np.flatnonzero((h.f == gen.READ) & h.ok)
    i = int(reads[np.argmin(np.abs(reads - frac * h.n_ops))])
    a = h.a.copy()
    a[i] = gen.BAD
    return dataclasses.replace(h, a=a)


def relabel(h: History, perm) -> History:
    return _with(h, gen.relabel(_base(h), perm))


def to_ops(h: History) -> list:
    """Jepsen op maps in event order; a crashed op's completion is an
    ``info`` carrying its invocation's value."""
    out = gen.to_ops(_base(h))
    for i in np.flatnonzero(h.crashed).tolist():
        inv, ret = out[int(h.inv[i])], out[int(h.ret[i])]
        out[int(h.ret[i])] = dict(ret, type="info", value=inv["value"])
    return out


class _Entries:
    def __init__(self, entries, crashed: np.ndarray):
        self._e, self._c = entries, crashed

    def __len__(self) -> int:
        return len(self._e)

    def __getitem__(self, i: int):
        e = self._e[i]
        return dataclasses.replace(e, crashed=True) if self._c[i] else e


class Packed(sut.Packed):
    """The program's packed arrays: a crashed op goes in whatever its
    CAS would have done, with ``crashed=True`` and ``ret_ev = inf_ev``;
    a timed-out read is left out, as a failed CAS."""

    def __init__(self, h: History):
        inf_ev = 2 * h.n_ops + 2
        super().__init__(gen.History(
            h.proc, h.f, h.a, h.b, h.ok | h.crashed, h.inv,
            np.where(h.crashed, inf_ev, h.ret)))
        self.crashed = np.ascontiguousarray(h.crashed[self.rows])

    def packed(self, perm=None):
        p = super().packed(perm)
        return dataclasses.replace(p, crashed=self.crashed,
                                   entries=_Entries(p.entries, self.crashed))


packer = Packed


def max_pending(h: History) -> int:
    """Most ops pending at once, a crashed op to the end."""
    keep = h.ok | h.crashed
    ret = np.where(h.crashed, 2 * h.n_ops, h.ret)
    ev = np.concatenate([h.inv[keep], ret[keep]])
    d = np.concatenate([np.ones(keep.sum(), np.int64),
                        -np.ones(keep.sum(), np.int64)])
    return int(np.max(np.cumsum(d[np.argsort(ev, kind="stable")]),
                      initial=0))


def work(h: History) -> dict:
    """The roofline count in the key's own width: R returns, W with
    crashed slots held to the end (M = 2**W), S states (a crashed CAS
    may install its new value)."""
    live = h.ok | h.crashed
    vals = set(h.a[h.f == gen.WRITE].tolist())
    vals |= set(h.b[(h.f == gen.CAS) & live].tolist())
    O = len({(int(f), int(a), int(b)) for f, a, b, x in
             zip(h.f.tolist(), h.a.tolist(), h.b.tolist(), live.tolist())
             if x})
    return _work.count(int((h.ok & ~h.crashed).sum()), max_pending(h),
                       1 + len(vals), O)


def check(h: History, skip_failing_op: bool = False) -> dict:
    """Just-in-time linearization as :func:`reference.check`, with every
    crashed op pending from its invocation to the end: it may be
    linearized at any later point, or never. ``skip_failing_op`` is
    the control: the previous return reported in the failing op's
    place."""
    live = [i for i, x in enumerate((h.ok | h.crashed).tolist()) if x]
    crashed = h.crashed.tolist()
    fl, al, bl = h.f.tolist(), h.a.tolist(), h.b.tolist()
    inv, ret = h.inv.tolist(), h.ret.tolist()
    events = sorted([(inv[i], 0, i) for i in live]
                    + [(ret[i], 1, i) for i in live if not crashed[i]])
    slot_of, pending = {}, {}                   # op -> slot, slot -> op
    configs = {(gen.NIL, 0)}
    last_ret = None
    for _, is_ret, i in events:
        if not is_ret:
            s = min(set(range(len(pending) + 1)) - set(pending))
            slot_of[i], pending[s] = s, i
            continue
        k = slot_of.pop(i)
        seen = set(configs)
        todo = list(configs)
        while todo:
            st, mask = todo.pop()
            for s, j in pending.items():
                if mask >> s & 1:
                    continue
                nst = reference.step(st, fl[j], al[j], bl[j])
                if nst is not None and (nst, mask | 1 << s) not in seen:
                    seen.add((nst, mask | 1 << s))
                    todo.append((nst, mask | 1 << s))
        configs = {(st, mask & ~(1 << k)) for st, mask in seen
                   if mask >> k & 1}
        del pending[k]
        if not configs:
            bad = last_ret if (skip_failing_op and last_ret is not None) \
                else i
            return {"valid": False, "op": op_key(h, bad)}
        last_ret = i
    return {"valid": True, "op": None}


# looked up in ``sut`` at each call, so that a test's stub of the
# program's entry points reaches this family too
def check_packed(packed) -> dict:
    return sut.check_packed(packed)


# a keyed batch with no verdict after this long is a hang: the run
# ends there, exit status 1 and every thread's stack on stderr, rather
# than hold the chip (a run's first batch, which compiles every shape,
# takes about five minutes on one v5e)
BATCH_DEADLINE_S = 600


def check_many(packed_list, devices=None, diag=None) -> list:
    faulthandler.dump_traceback_later(BATCH_DEADLINE_S, exit=True)
    try:
        return sut.check_many(packed_list, devices=devices, diag=diag)
    finally:
        faulthandler.cancel_dump_traceback_later()
