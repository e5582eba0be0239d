"""Median host time of an engine tick, in ms: over the ``engine.tick`` spans
that end in the window, the tick's duration minus the time its ``*.wait``
spans cover (the host blocked on the device's results).  Read from the
program's own spans (``repro.spans``); nothing when the program has none."""

import numpy as np

from bench.program_spans import load, tick_host_s


def read(rec, tr):
    spans = load(rec)
    host = tick_host_s(spans, rec["record"]["window"]) if spans else []
    return 1e3 * float(np.median(host)) if host else None
