"""Prompt plus generated tokens of the requests completed in the window,
over the window."""

from bench.stats import completed_in_window


def read(rec, tr):
    r = rec["record"]
    done = completed_in_window(r)
    w0, w1 = r["window"]
    return sum(x["prompt_len"] + x["n_gen"] for x in done) / (w1 - w0)
