"""Per cent of the config rows the keyed batch's lockstep groups walk
at their group's mask width that no key needed at its own: 100 * (1 -
sum own_rows / sum mask_rows) over the window's checks, from each
dispatch group's entry in ``diag["groups"]`` (``mask_rows`` = 2**W of
the group x the returns of its keys; ``own_rows`` = the sum over its
keys of 2**W_key x the key's returns). Only mask padding counts: pad
lanes and padded returns do not. None where the program reports no
such rows."""


def read(ctx):
    rows = own = 0
    for r in ctx["records"]:
        for g in (r.get("diag") or {}).get("groups", []):
            if "mask_rows" in g and "own_rows" in g:
                rows += g["mask_rows"]
                own += g["own_rows"]
    if rows <= 0:
        return None
    return 100.0 * (1.0 - own / rows)
