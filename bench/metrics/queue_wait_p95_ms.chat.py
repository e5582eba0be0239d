"""p95 wait from a request's due time to the start of the engine tick that
admitted it (the scheduler's ``admit_tick`` mapped onto the harness's tick
stamps), over the requests due in the window; never admitted counts +inf."""

from bench.stats import measured, nearest_rank, tick_starts


def read(rec, tr):
    r = rec["record"]
    starts = tick_starts(r)
    waits = [float(starts[x["admit_tick"]] - x["due"]) if x.get("admit_tick") is not None
             else float("inf") for x in measured(r)]
    return 1e3 * nearest_rank(waits, 0.95) if waits else None
