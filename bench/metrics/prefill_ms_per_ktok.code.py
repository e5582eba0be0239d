"""Device milliseconds of the engine's one-shot prefill programs (packed and
single, ``jit_fn``) per thousand prompt tokens they ingested in the window."""

from bench.stats import tick_ends
from bench.trace_reduce import module_durations


def read(rec, tr):
    r = rec["record"]
    d = module_durations(tr, r"^jit_fn\b")
    ends = tick_ends(r)
    w0, w1 = r["window"]
    toks = sum(x["prompt_len"] for x in r["requests"]
               if x.get("token_ticks") and w0 <= ends[x["token_ticks"][0]] < w1)
    if not len(d) or not toks:
        return None
    return 1e3 * float(d.sum()) / (toks / 1e3)
