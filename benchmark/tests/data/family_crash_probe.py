"""A crash-mix history family, the probe that shows a configuration can
bring histories of its own kind as new files alone (the hook itself is
described in ``benchmark/families/cas.py``).

The default family's CAS-register histories, with the return of every
``crash_every``-th write or CAS dropped: that op ends ``info`` (the
client timed out and cannot say whether it took effect) and stays
pending to the end of the history. As Jepsen replaces a crashed
client's process, the crashed op is given a fresh process id, so no
process has two ops pending. Reads are never crashed (a timed-out read
ends ``fail`` and is left out). Crashing an op only widens what a
linearization may do, so a valid history stays valid, and a corrupted
read stays unexplainable.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import numpy as np

from benchmark import gen, reference, sut, work as _work

model_name = "cas-register"
perm_of = gen.perm_of
op_key = reference.op_key


@dataclass(frozen=True)
class History(gen.History):
    """``crashed``: the op never returned; its ``ret`` is the position
    of its ``info`` completion."""
    crashed: np.ndarray


def _base(h: History) -> gen.History:
    return gen.History(h.proc, h.f, h.a, h.b, h.ok, h.inv, h.ret)


def _with(h: History, g: gen.History) -> History:
    return History(g.proc, g.f, g.a, g.b, g.ok, g.inv, g.ret, h.crashed)


def history(rng, cfg: dict, n_ops: int) -> History:
    g = gen.cas_history(rng, n_ops, cfg["processes"], cfg["values"])
    k = int(cfg["crash_every"])
    crashed = np.zeros(g.n_ops, bool)
    crashed[np.flatnonzero(g.f != gen.READ)[k - 1::k]] = True
    proc = g.proc.copy()
    proc[crashed] = cfg["processes"] + np.arange(int(crashed.sum()))
    return History(proc, g.f, g.a, g.b, g.ok, g.inv, g.ret, crashed)


def corrupt_at(rng, h: History, frac) -> History:
    """The read nearest ``frac`` (or one drawn from ``rng``) reads a
    value nobody wrote; reads are never crashed."""
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
    CAS would have done, with ``crashed=True`` and ``ret_ev = inf_ev``."""

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


def _max_pending(h: History) -> int:
    """Most ops pending at once, a crashed op to the end."""
    keep = h.ok | h.crashed
    ret = np.where(h.crashed, 2 * h.n_ops, h.ret)
    ev = np.concatenate([h.inv[keep], ret[keep]])
    d = np.concatenate([np.ones(keep.sum(), np.int64),
                        -np.ones(keep.sum(), np.int64)])
    return int(np.max(np.cumsum(d[np.argsort(ev, kind="stable")]),
                      initial=0))


def work(h: History) -> dict:
    """The roofline count: R returns, W with crashed slots held to the
    end, S states (a crashed CAS may install its new value)."""
    live = h.ok | h.crashed
    vals = set(h.a[h.f == gen.WRITE].tolist())
    vals |= set(h.b[(h.f == gen.CAS) & live].tolist())
    O = len({(int(f), int(a), int(b)) for f, a, b, x in
             zip(h.f.tolist(), h.a.tolist(), h.b.tolist(), live.tolist())
             if x})
    return _work.count(int((h.ok & ~h.crashed).sum()), _max_pending(h),
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


def check_many(packed_list, devices=None, diag=None) -> list:
    return sut.check_many(packed_list, devices=devices, diag=diag)
