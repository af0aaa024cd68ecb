"""Shared arithmetic of the readers of the program's parented spans
(not a metric itself): unions of span intervals over the window, and
under the root spans that name a request in their ``reqs``.

A program whose spans carry no ``root`` predates these spans: every
reader then returns None."""
from __future__ import annotations


def parented(ctx) -> bool:
    """Whether the program's spans carry ``id``/``parent``/``root``."""
    return any("root" in (s.get("args") or {}) for s in ctx["spans"])


def union_us(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def window_share(ctx, match):
    """Per cent of the window covered by the union of the spans whose
    name ``match`` accepts (clipped to the window)."""
    if not parented(ctx):
        return None
    t0, t1 = ctx["obs_window_us"]
    iv = [(max(s["ts"], t0), min(s["ts"] + s["dur"], t1))
          for s in ctx["spans"] if match(s["name"])]
    return 100.0 * union_us([(a, b) for a, b in iv if b > a]) / (t1 - t0)


def per_request_s(ctx, match):
    """For each request the cell sent, the seconds of the union of
    the spans accepted by ``match`` under every root whose ``reqs``
    holds its id; requests with no such root among the window's spans
    are left out. None when the program's spans carry no roots."""
    if not parented(ctx):
        return None
    roots, by_root = {}, {}
    for s in ctx["spans"]:
        a = s.get("args") or {}
        for r in a.get("reqs") or ():
            roots.setdefault(r, set()).add(a["id"])
        if match(s["name"]):
            by_root.setdefault(a["root"], []).append(
                (s["ts"], s["ts"] + s["dur"]))
    out = []
    for r in getattr(ctx["driver"], "sent", None) or ():
        ids = roots.get(r.get("id"))
        if ids:
            out.append(1e-6 * union_us(
                [iv for i in ids for iv in by_root.get(i, ())]))
    return out


def is_memo(name: str) -> bool:
    return name.startswith("memo.")


def is_witness(name: str) -> bool:
    return name == "reach.witness"
