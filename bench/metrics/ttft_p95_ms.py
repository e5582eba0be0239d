"""p95 time to first token over the requests due in the window, timed from
when each was due; a request that never got one counts as +inf."""

from bench.stats import nearest_rank, ttfts


def read(rec, tr):
    return 1e3 * nearest_rank(ttfts(rec["record"]), 0.95)
