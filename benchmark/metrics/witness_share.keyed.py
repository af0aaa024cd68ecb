"""Per cent of the window the host spent decoding failed keys'
witnesses: the union of the program's ``reach.witness`` spans (each
failed key's own-geometry prep, memo lookups and witness walk)."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.window_share(ctx, _spans.is_witness)
