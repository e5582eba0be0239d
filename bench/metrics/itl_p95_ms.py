"""p95 gap between consecutive tokens of a request, over every gap that
closes in the window; a token's time is the end of the tick that returned
it."""

from bench.stats import inter_token_gaps, nearest_rank


def read(rec, tr):
    gaps = inter_token_gaps(rec["record"])
    return 1e3 * nearest_rank(gaps, 0.95) if gaps else None
