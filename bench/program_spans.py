"""The serving engine's own spans (``repro.spans``) in a traced run.

The engine keeps its spans on ``time.perf_counter()``, the clock of the
harness's tick stamps (``record["ticks"]``); the profile has a clock of its
own.  ``to_trace_clock`` moves the spans onto the profile's clock through the
benchmark's ``engine_step`` spans, which bracket the same ticks.  A program
without ``repro.spans`` has no spans: ``load`` gives None and the readers that
use it report nothing.
"""

from __future__ import annotations

import statistics

import numpy as np

from bench.trace_reduce import clip, union

MAX_SPREAD_S = 100e-6  # widest spread of the per-tick clock offsets accepted
QUEUED = "request.queued"  # a wait in the queue, not something the host does


def load(rec):
    """The run's program spans (``repro.spans.take()``, taken once and kept
    in ``rec`` so that every reader sees the same list), or None when the
    program has no spans."""
    if "program_spans" not in rec:
        try:
            from repro import spans
        except ImportError:
            rec["program_spans"] = None
        else:
            rec["program_spans"] = spans.take()
    return rec["program_spans"]


def clock_offset(rec, tr):
    """(offset, spread) in seconds: the profile's clock minus the program's,
    the median over the window's ticks of the ``engine_step`` start in the
    profile minus the matching tick start in the record, and the spread of
    those offsets (the distance between their quartiles); None when no tick
    matches."""
    h = tr.host
    steps = np.array([s for n, s in zip(h.names, h.start)
                      if n == "engine_step" and tr.window[0] <= s <= tr.window[1]])
    ticks = np.sort(np.array([t[0] for t in rec["record"]["ticks"]], float))
    if len(steps) < 2 or not len(ticks):
        return None
    # the bench_window span opens right after the record's window stamp: a
    # first guess, good to microseconds, of which tick each engine_step is
    guess = tr.window[0] - rec["record"]["window"][0]
    j = np.clip(np.searchsorted(ticks, steps - guess), 1, len(ticks) - 1)
    j = np.where(np.abs(ticks[j - 1] + guess - steps) < np.abs(ticks[j] + guess - steps),
                 j - 1, j)
    off = steps - ticks[j]
    q1, _, q3 = statistics.quantiles(off.tolist(), n=4)
    return float(np.median(off)), q3 - q1


def to_trace_clock(rec, tr, spans):
    """``spans`` with their start and end on the profile's clock, as tuples
    ``(name, start, end, parent, args)`` in the same order (``parent`` indices
    hold); None when the offsets spread by more than ``MAX_SPREAD_S``."""
    found = clock_offset(rec, tr)
    if found is None or found[1] > MAX_SPREAD_S:
        return None
    off = found[0]
    return [(n, s + off, e + off, p, a) for n, s, e, p, a in spans]


def innermost(spans, t, order=None):
    """Index of the innermost span (``request.queued`` aside) open at ``t``,
    or -1.  ``order`` is ``_by_start(spans)``, kept across calls."""
    idx, starts = order if order is not None else _by_start(spans)
    k = int(np.searchsorted(starts, t, side="right")) - 1
    i = idx[k] if k >= 0 else -1
    while i >= 0 and spans[i][2] < t:
        i = spans[i][3]
    return i


def _by_start(spans):
    idx = [i for i, s in enumerate(spans) if s[0] != QUEUED]
    idx.sort(key=lambda i: spans[i][1])
    return idx, np.array([spans[i][1] for i in idx], float)


def idle_by_phase(tr, spans):
    """Device idle seconds in the window, by what the host was in at each
    idle gap's middle: the innermost program span (on the profile's clock,
    from ``to_trace_clock``), else the innermost benchmark span
    (``engine_step``, ``generator``, ...), else ``no span``.  Largest first."""
    dev = sorted(tr.ops)[0]
    iv = clip(union(tr.ops[dev].start, tr.ops[dev].end), *tr.window)
    edges = [tr.window[0]] + [x for s, e in iv for x in (s, e)] + [tr.window[1]]
    order = _by_start(spans)
    h = tr.host
    bench = [i for i, n in enumerate(h.names) if n != "bench_window"]
    out = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        i = innermost(spans, mid, order)
        if i >= 0:
            name = spans[i][0]
        else:
            inside = [k for k in bench if h.start[k] <= mid <= h.end[k]]
            name = (h.names[min(inside, key=lambda k: h.end[k] - h.start[k])]
                    if inside else "no span")
        out[name] = out.get(name, 0.0) + (b - a)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def tick_host_s(spans, window):
    """Per ``engine.tick`` ending in ``window`` (the program's clock): its
    duration minus the time its ``*.wait`` spans cover, in seconds."""
    waited = {}
    for n, s, e, p, _ in spans:
        if n.endswith(".wait"):
            while p >= 0 and spans[p][0] != "engine.tick":
                p = spans[p][3]
            if p >= 0:
                waited[p] = waited.get(p, 0.0) + (e - s)
    return [e - s - waited.get(i, 0.0) for i, (n, s, e, _, _) in enumerate(spans)
            if n == "engine.tick" and window[0] <= e < window[1]]


def queued_s(spans):
    """rid -> seconds from ``submit()`` to the admission that gave it a slot."""
    return {a["rid"]: e - s for n, s, e, _, a in spans if n == QUEUED}
