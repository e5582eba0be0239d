"""A serving cell: the program's ``ServeEngine`` under a request stream.

Set-up builds the engine on bench-made weights, warms every prefill shape
the traffic can produce and the decode step, then plays the traffic's
warm-up seconds.  The window submits requests as they fall due and ticks
the engine; every tick's start and end are stamped on the host clock, and a
token's time is the end of the tick that returned it.  After the window the
engine keeps ticking, without new requests, until every request due in the
window has finished or ``TAIL_S`` has passed since the close, so late
answers count as late; a request still decoding then keeps the tokens it
has.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import FP8, REFERENCE, Arch, Reference
from bench.model import abstract_params, model_config
from bench.trace_reduce import trace_options
from bench.weights import make_weights

TAIL_S = 60.0


def serve_config(cfg_json: dict):
    from repro.serve.config import ServeConfig

    kw = dict(cfg_json["serve"])
    kw["cache_dtype"] = jnp.dtype(kw["cache_dtype"])
    return ServeConfig(**kw)


def prefill_shapes(scfg, spec: dict):
    """(k, bucket) pairs the engine's packed one-shot prefill can launch
    for prompts in [prompt.min, prompt.max]: k prompts whose total falls in
    the bucket."""
    from repro.serve.scheduler import default_buckets

    buckets = scfg.prefill_buckets or default_buckets(scfg.max_seq, 1)
    lo, hi = spec["prompt"]["min"], spec["prompt"]["max"]
    out, prev = [], 0
    for b in sorted(buckets):
        for k in range(1, scfg.pack_max + 1):
            if k * lo <= b and min(k * hi, scfg.max_seq) > prev and b <= scfg.max_seq:
                out.append((k, b))
        prev = b
    return out


class Tracked:
    """What the harness knows of one request."""

    __slots__ = ("req", "due", "submit", "result", "measured")

    def __init__(self, req, due, submit, measured):
        self.req, self.due, self.submit = req, due, submit
        self.result = None
        self.measured = measured


class ServeCell:
    def __init__(self, cfg_json, traffic, seed, seconds):
        from repro.parallel.context import ParallelCtx
        from repro.serve.engine import ServeEngine

        self.cfg_json, self.traffic, self.seed, self.seconds = cfg_json, traffic, seed, seconds
        self.cfg = model_config(cfg_json)
        self.scfg = serve_config(cfg_json)
        self.dtype = jnp.dtype(cfg_json["param_dtype"])
        params = make_weights(abstract_params(self.cfg, self.dtype), seed, self.dtype)
        jax.block_until_ready(params)
        self.engine = ServeEngine(self.cfg, params, ctx=ParallelCtx(), serve=self.scfg)
        del params
        self.ticks = []  # (start, end) host clock of every engine.step()
        self.tracked = {}  # rid -> Tracked

    # -- driving the engine --------------------------------------------------

    def _step(self):
        with jax.profiler.TraceAnnotation("engine_step"):
            t0 = time.perf_counter()
            done = self.engine.step()
            t1 = time.perf_counter()
        self.ticks.append((t0, t1))
        return done, t1

    def _drain(self):
        while self.engine.has_work:
            self._step()

    def warm_shapes(self):
        """Run every prefill shape of the traffic once, and the decode step."""
        rng = np.random.default_rng([self.seed, 0x3A11])
        vocab = self.cfg.vocab_size
        shapes = prefill_shapes(self.scfg, self.traffic)
        for k, bucket in shapes:
            total = bucket - 1  # fills the bucket, leaves room for a token
            lens = [total // k] * k
            lens[0] += total - sum(lens)
            for n in lens:
                self.engine.submit(rng.integers(0, vocab, n, dtype=np.int32),
                                   max_new_tokens=1)
            self._drain()
        lo = self.traffic["prompt"]["min"]  # a prefill shape warmed above
        self.engine.submit(rng.integers(0, vocab, lo, dtype=np.int32), max_new_tokens=3)
        self._drain()
        return shapes

    def play(self, source, t_zero, until, measured_from, stop_submitting):
        """Submit what falls due and tick, until the host clock passes
        ``until``."""
        eng = self.engine
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            if not stop_submitting:
                with jax.profiler.TraceAnnotation("generator"):
                    due = source.pending(now - t_zero)
                for r in due:
                    with jax.profiler.TraceAnnotation("submit"):
                        rid = eng.submit(r.prompt, max_new_tokens=r.max_new)
                    self.tracked[rid] = Tracked(r, t_zero + r.due, time.perf_counter(),
                                                t_zero + r.due >= measured_from)
            if eng.has_work:
                done, t1 = self._step()
                for res in done:
                    tr = self.tracked[res.rid]
                    tr.result = res
                    source.finished(tr.req, t1 - t_zero)
            elif stop_submitting:
                return
            else:
                nd = source.next_due()
                wake = until if nd is None else min(until, t_zero + nd)
                time.sleep(max(0.0, wake - time.perf_counter()))

    def run(self, source, *, trace_dir=None):
        """Traffic warm-up, then the window; returns the record."""
        warm_s = source.warm_s
        t_zero = time.perf_counter()
        self.play(source, t_zero, t_zero + warm_s, measured_from=float("inf"),
                  stop_submitting=False)
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir, profiler_options=trace_options())
        w0 = time.perf_counter()
        w1 = w0 + self.seconds
        with jax.profiler.TraceAnnotation("bench_window"):
            self.play(source, t_zero, w1, measured_from=w0, stop_submitting=False)
        w_end = time.perf_counter()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        # the tail: no new requests; finish what the window let in
        deadline = w_end + TAIL_S
        while self.engine.has_work and time.perf_counter() < deadline:
            if all(t.result is not None for t in self.tracked.values() if t.measured):
                break
            done, _ = self._step()
            for res in done:
                self.tracked[res.rid].result = res
        return {
            "kind": "serve",
            "window": (w0, w1),
            "t_zero": t_zero,
            "ticks": self.ticks,
            "requests": [self._req_record(rid, tr) for rid, tr in self.tracked.items()],
        }

    def _req_record(self, rid, tr):
        """A finished request's result; one still decoding when the tail
        ended is ``in_flight`` with the tokens it has (late, not lost)."""
        r = tr.result
        rec = {"rid": rid, "due": tr.due, "submit": tr.submit, "measured": tr.measured,
               "prompt_len": int(len(tr.req.prompt)), "max_new": tr.req.max_new,
               "status": None if r is None else r.status}
        if r is None:
            live = self.engine.scheduler.find(rid)
            if live is not None and live.generated:
                rec.update(status="in_flight", admit_tick=live.admit_tick,
                           token_ticks=list(live.token_ticks), finish_tick=None,
                           n_gen=len(live.generated))
        else:
            rec.update(admit_tick=r.admit_tick, token_ticks=list(r.token_ticks),
                       finish_tick=r.finish_tick, n_gen=int(len(r.tokens)))
        return rec

    # -- correctness ---------------------------------------------------------

    def check_sample(self, record):
        """Requests due in the window and finished, drawn from the seed, the
        longest-output one first, until ``check.min_tokens`` served tokens
        or ``check.max_requests`` requests."""
        spec = self.traffic["check"]
        done = [(rid, tr) for rid, tr in self.tracked.items()
                if tr.measured and tr.result is not None and tr.result.status == "ok"]
        if not done:
            return []
        rng = np.random.default_rng([self.seed, 0xC4EC])
        longest = max(done, key=lambda x: len(x[1].result.tokens))
        rest = [done[i] for i in rng.permutation(len(done)) if done[i] is not longest]
        out, tokens = [], 0
        for rid, tr in [longest] + rest:
            if tokens >= spec["min_tokens"] or len(out) >= spec["max_requests"]:
                break
            out.append((tr.req.prompt, np.asarray(tr.result.tokens, np.int32)))
            tokens += len(tr.result.tokens)
        return out

    def free(self):
        """Drop the engine and everything it holds on the device."""
        self.engine = None
        gc.collect()


def served_gaps(cfg_json, seed, sample, *, control=False):
    """For each served token: how far its logit lies below the best logit of
    the float32 reference at that position.  Returns the widest such gap,
    the tokens compared, and with ``control`` two readings for the limit:
    the widest gap of the tokens that the float8 control puts first, and
    that of the served tokens altered (id + 1), a fault planted at the
    output."""
    arch = Arch.from_config(cfg_json)
    dtype = jnp.dtype(cfg_json["param_dtype"])
    cfg = model_config(cfg_json)
    params = make_weights(abstract_params(cfg, dtype), seed, dtype)
    S = cfg_json["serve"]["max_seq"]
    ref = Reference(arch, REFERENCE)
    ctl = Reference(arch, FP8)

    @jax.jit
    def ref_fn(p, toks, targets):
        lf = ref.logits(p, toks[None])[0]
        best = jnp.max(lf, -1)
        return best[None] - jnp.take_along_axis(lf[None], targets[..., None], -1)[..., 0]

    @jax.jit
    def ctl_fn(p, toks):
        return jnp.argmax(ctl.logits(p, toks[None])[0], -1).astype(jnp.int32)

    worst = np.zeros(3)
    n = 0
    for prompt, gen in sample:
        seq = np.concatenate([prompt, gen])
        toks = np.zeros(S, np.int32)
        toks[: len(seq)] = seq
        nxt = np.zeros(S, np.int32)
        nxt[: len(seq) - 1] = seq[1:]
        lo, hi = len(prompt) - 1, len(seq) - 1  # positions that predicted gen
        toks_d, nxt_d = jnp.asarray(toks), jnp.asarray(nxt)
        targets = [nxt_d]
        if control:
            targets += [ctl_fn(params, toks_d), (nxt_d + 1) % arch.vocab]
        gaps = np.asarray(ref_fn(params, toks_d, jnp.stack(targets)))[:, lo:hi]
        worst[: len(targets)] = np.maximum(worst[: len(targets)], gaps.max(-1))
        n += hi - lo
    if control:
        return float(worst[0]), n, {"fp8_control": float(worst[1]),
                                    "altered_token": float(worst[2])}
    return float(worst[0]), n, None
