"""Model FLOPs served in the window (2 N per prompt or generated token plus
causal attention; ``bench/costs/step.py``) over window x chips x bf16 peak,
in %."""

from bench.stats import served_flops


def read(rec, tr):
    r = rec["record"]
    w0, w1 = r["window"]
    return 100.0 * served_flops(r, rec["arch"]) / ((w1 - w0) * rec["chips"] * rec["peak_flops"])
