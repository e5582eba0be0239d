"""Arithmetic shared by the metric readers: percentiles and the host-clock
times of a serving run's requests and tokens."""

from __future__ import annotations

import math

import numpy as np


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank (a value that occurred; a missing
    request counts as +inf)."""
    v = sorted(values)
    if not v:
        return float("nan")
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def tick_ends(record):
    return np.array([t[1] for t in record["ticks"]])


def tick_starts(record):
    return np.array([t[0] for t in record["ticks"]])


def measured(record):
    """Requests due in the window."""
    return [r for r in record["requests"] if r["measured"]]


def ttfts(record):
    """Seconds from each measured request's due time to the end of the tick
    that returned its first token; +inf for one that never got one."""
    ends = tick_ends(record)
    out = []
    for r in measured(record):
        if r["status"] not in ("ok", "in_flight") or not r.get("token_ticks"):
            out.append(float("inf"))
        else:
            out.append(float(ends[r["token_ticks"][0]] - r["due"]))
    return out


def inter_token_gaps(record):
    """Gaps between consecutive tokens of a request, for every gap that
    closes inside the window (the idea of ``serve_bench._inter_token_gaps``,
    on the host clock: a token's time is the end of its tick)."""
    ends = tick_ends(record)
    w0, w1 = record["window"]
    out = []
    for r in record["requests"]:
        t = ends[np.asarray(r.get("token_ticks") or [], int)]
        if len(t) > 1:
            g = np.diff(t)
            out.extend(g[(t[1:] >= w0) & (t[1:] < w1)].tolist())
    return out


def completed_in_window(record):
    ends = tick_ends(record)
    w0, w1 = record["window"]
    return [r for r in record["requests"]
            if r["status"] == "ok" and r.get("finish_tick") is not None
            and w0 <= ends[r["finish_tick"]] < w1]


def served_flops(record, arch):
    """Model FLOPs of the tokens served in the window: each prompt whose
    prefill tick and each generated token whose decode tick ended in it."""
    from bench.costs.step import decode_flops, prefill_flops

    ends = tick_ends(record)
    w0, w1 = record["window"]
    flops = 0.0
    for x in record["requests"]:
        for j, t in enumerate(x.get("token_ticks") or []):
            if w0 <= ends[t] < w1:
                flops += (prefill_flops(arch, x["prompt_len"]) if j == 0
                          else decode_flops(arch, x["prompt_len"] + j))
    return flops
