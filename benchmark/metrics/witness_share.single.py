"""Per cent of the traced window spent decoding failed verdicts'
witnesses: the union of the program's ``reach.witness`` spans (0 when
no check of the traced window was invalid)."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.window_share(ctx, _spans.is_witness)
