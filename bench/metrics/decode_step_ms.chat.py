"""Median device time of the engine's jitted decode step (program
``jit__decode_traced``) in the traced window."""

import numpy as np

from bench.trace_reduce import module_durations


def read(rec, tr):
    d = module_durations(tr, r"_decode_traced")
    return 1e3 * float(np.median(d)) if len(d) else None
