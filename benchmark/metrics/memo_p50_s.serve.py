"""Median, by nearest rank, over the window's requests whose dispatch
root span was recorded, of the seconds the memo tier took for the
request: the union of the ``memo.*`` spans under the roots
(``serve.dispatch``, ``serve.dispatch-host``, ``pipeline.stage``)
whose ``reqs`` holds the request's id."""
from benchmark.metrics import _share, _spans


def read(ctx):
    per = _spans.per_request_s(ctx, _spans.is_memo)
    return _share.nearest_rank(per, 0.5) if per else None
