"""Per cent of the window the host spent in the memo tier: the union of
the program's ``memo.*`` spans (BFS builds, disk-tier gets and puts
with their eviction scan), wherever they nest."""
from benchmark.metrics import _spans


def read(ctx):
    return _spans.window_share(ctx, _spans.is_memo)
