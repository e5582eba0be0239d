"""One run of a cell at the tiny size on the CPU, with the harness's look for
a chip skipped and, optionally, the timed path broken underneath:

    python bench/tests/fault_run.py <workload> <fault> <trace 0|1>

faults: ``none``; ``altered_token`` (every decoded token + 1);
``stale_cache`` (the decode step hands back the KV pool it was given, so
the keys and values of decoded tokens are never stored).
Prints the run's output; the last line is the result."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run  # noqa: E402
from bench.tests.tiny import adjust  # noqa: E402


def altered_token(sc):
    eng, vocab = sc.engine, sc.cfg.vocab_size
    orig = eng._decode

    def bad(params, cache, tokens):
        nxt, cache, logits, ok = orig(params, cache, tokens)
        return (nxt + 1) % vocab, cache, logits, ok

    eng._decode = bad


def stale_cache(sc):
    eng = sc.engine
    orig = eng._decode

    def stale(params, cache, tokens):
        nxt, _, logits, ok = orig(params, cache, tokens)
        return nxt, cache, logits, ok

    eng._decode = stale


FAULTS = {"none": None, "altered_token": altered_token, "stale_cache": stale_cache}

if __name__ == "__main__":
    workload, fault, trace = sys.argv[1:4]
    sys.exit(run.main(["--workload", workload, "--seed", str(2**33 + 5), "--seconds", "2",
                       "--trace", trace], require_tpu=False, adjust=adjust,
                      plant=FAULTS[fault]))
