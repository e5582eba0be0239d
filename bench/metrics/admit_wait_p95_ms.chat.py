"""p95 (nearest rank) wait in the engine's queue, in ms: the ``request.queued``
span of each request submitted in the window, from ``submit()`` to the
admission that gave it a slot; a request never admitted counts as +inf.  Read
from the program's own spans (``repro.spans``); nothing when the program has
none."""

from bench.program_spans import load, queued_s
from bench.stats import nearest_rank


def read(rec, tr):
    spans = load(rec)
    if not spans:
        return None
    r = rec["record"]
    w0, w1 = r["window"]
    queued = queued_s(spans)
    waits = [queued.get(x["rid"], float("inf")) for x in r["requests"]
             if w0 <= x["submit"] < w1]
    return 1e3 * nearest_rank(waits, 0.95) if waits else None
