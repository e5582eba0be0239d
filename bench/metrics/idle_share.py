"""Share of the traced window in which no op ran on the device, in %."""

from bench.trace_reduce import busy_mean, window_s


def read(rec, tr):
    return 100.0 * (1.0 - busy_mean(tr) / window_s(tr))
