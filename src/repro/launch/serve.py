"""Serving entry point: static-batch generation or streaming continuous
batching over the slot-pool engine.

    # static batch (legacy)
    PYTHONPATH=src python -m repro.launch.serve --arch minicpm3-4b --reduced \
        [--fake-devices 8] [--batch 4] [--prompt-len 16] [--new-tokens 8]

    # streaming: replay a mixed-length arrival trace through the scheduler
    PYTHONPATH=src python -m repro.launch.serve --reduced --stream \
        [--fake-devices 8] [--trace 16:0,32:1,64:2,16:4] [--slots 4]

``--trace`` is a comma list of ``prompt_len[:arrival_tick]`` items; slots at
different depths decode in a single jitted step per tick.  Add
``--prefill-chunk 64 [--tick-token-budget 128]`` to ingest prompts through
the continuous-prefill path, interleaved with decode.

Robustness knobs: ``--oversubscribe 1.5`` admits against 1.5x the physical
page pool (preempt-and-recompute under pressure), ``--deadline-ticks`` /
``--cancel idx:tick`` exercise the lifecycle paths, ``--chaos-seed N``
replays a seeded fault trace (squeezes + NaN ticks + dropped grants), and
``--check-deterministic`` reruns everything and exits 1 unless statuses,
streams, and chaos events reproduce exactly — the CI chaos-smoke gate;
``--check-preempts`` exits 1 unless the run preempted at least once.

On ``n`` devices the sequence axis spans all of them, and one logical page
holds ``n * page_size`` tokens: keep that product fixed when a trace's page
geometry matters (``--fake-devices 8 --page-size 2`` pages 16 tokens).
"""

import argparse
import json
import os
import sys


def _parse_trace(spec: str):
    items = []
    for part in spec.split(","):
        if ":" in part:
            ln, tick = part.split(":")
        else:
            ln, tick = part, 0
        items.append((int(ln), int(tick)))
    return items


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="continuous batching: replay --trace through the scheduler")
    ap.add_argument("--trace", default="16:0,32:1,64:2,16:4",
                    help="comma list of prompt_len[:arrival_tick]")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache (block tables + prefix sharing)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="local positions per page (paged mode)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="physical pool size in pages (paged mode)")
    ap.add_argument("--decode-kernel", default="auto",
                    choices=("auto", "native", "gather"),
                    help="flash-decode variant: auto (paged -> the native "
                         "paged kernel), native, or the gather oracle")
    ap.add_argument("--kv-dtype", default="fp", choices=("fp", "int8", "fp8"),
                    help="paged-pool storage: fp keeps cache_dtype; int8/fp8 "
                         "store quantized pages + per-(token, kv-head) f32 "
                         "scales, dequantized in-kernel (requires --paged)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous prefill: ingest prompts in chunks of "
                         "this many tokens, interleaved with decode")
    ap.add_argument("--tick-token-budget", type=int, default=None,
                    help="cap decode+prefill-chunk tokens per tick "
                         "(requires --prefill-chunk)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decode: verify up to this many tokens "
                         "per slot per tick (0 disables; needs >= 2)")
    ap.add_argument("--spec-draft", default="ngram", choices=("ngram", "off"),
                    help="draft proposer for speculative decode")
    ap.add_argument("--spec-max-misses", type=int, default=4,
                    help="suspend a slot's drafting after this many "
                         "consecutive zero-accept verify ticks (0 = never)")
    ap.add_argument("--check-spec-identical", action="store_true",
                    help="replay the --stream trace again with spec_k=0 and "
                         "exit nonzero unless every token stream matches")
    ap.add_argument("--oversubscribe", type=float, default=1.0,
                    help="admit against this multiple of the physical page "
                         "pool; > 1.0 enables preempt-and-recompute under "
                         "pressure (requires --paged and --prefill-chunk)")
    ap.add_argument("--deadline-ticks", type=int, default=None,
                    help="retire every request (status 'deadline', partial "
                         "tokens kept) this many ticks after its arrival")
    ap.add_argument("--health-every", type=int, default=0,
                    help="run the engine.health() invariant sweep every N "
                         "ticks (0 = only on demand)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="inject a seeded deterministic fault trace (pool "
                         "squeezes + NaN ticks + dropped grants) from "
                         "testing/chaos.py")
    ap.add_argument("--chaos-ticks", type=int, default=24,
                    help="horizon the chaos event schedule is drawn over")
    ap.add_argument("--cancel", default=None,
                    help="comma list of request_index:tick cancellations "
                         "applied during the --stream replay")
    ap.add_argument("--check-deterministic", action="store_true",
                    help="replay the whole --stream run (same seed, fresh "
                         "engine + fresh chaos injector) and exit nonzero "
                         "unless statuses, token streams, and chaos events "
                         "all match exactly")
    ap.add_argument("--check-preempts", action="store_true",
                    help="exit nonzero unless the --stream run preempted at "
                         "least one request (a pressure trace that never "
                         "exhausts the pool gates nothing)")
    args = ap.parse_args()

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import launch_context
    from repro.models import transformer as tfm
    from repro.serve.config import ServeConfig
    from repro.serve.engine import ServeEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    enable_compile_cache()
    ctx = launch_context(jax.device_count())
    params = tfm.init_params(cfg, jax.random.PRNGKey(0), ctx=ctx)
    def make_serve(spec_k):
        return ServeConfig(
            max_seq=args.max_seq, num_slots=args.slots, paged=args.paged,
            page_size=args.page_size, num_pages=args.num_pages,
            decode_kernel=args.decode_kernel, kv_dtype=args.kv_dtype,
            prefill_chunk=args.prefill_chunk,
            tick_token_budget=args.tick_token_budget,
            spec_k=spec_k, spec_draft=args.spec_draft,
            spec_max_misses=args.spec_max_misses or None,
            oversubscribe=args.oversubscribe,
            health_every=args.health_every,
        )

    def make_chaos():
        if args.chaos_seed is None:
            return None
        from repro.testing.chaos import ChaosConfig, ChaosInjector
        return ChaosInjector(ChaosConfig(seed=args.chaos_seed,
                                         ticks=args.chaos_ticks))

    chaos = make_chaos()
    eng = ServeEngine(cfg, params, ctx=ctx, serve=make_serve(args.spec_k),
                      chaos=chaos)
    rng = np.random.default_rng(0)

    if args.stream:
        trace = _parse_trace(args.trace)
        prompts = [
            rng.integers(0, cfg.vocab_size, (ln,), dtype=np.int32)
            for ln, _ in trace
        ]
        cancels = {}
        if args.cancel:
            for part in args.cancel.split(","):
                idx, t = part.split(":")
                cancels.setdefault(int(t), []).append(int(idx))

        def replay(engine, quiet=False):
            rids = [
                engine.submit(p, max_new_tokens=args.new_tokens,
                              arrival_tick=tick,
                              deadline_ticks=args.deadline_ticks)
                for p, (_, tick) in zip(prompts, trace)
            ]
            ticks = 0
            while engine.has_work:
                for idx in cancels.get(engine._tick, []):
                    engine.cancel(rids[idx])
                for req in engine.step():
                    if not quiet:
                        print(
                            f"rid={req.rid} len={len(req.prompt)} slot={req.slot} "
                            f"arrived@{req.arrival_tick} admitted@{req.admit_tick} "
                            f"finished@{req.finish_tick} status={req.status}: "
                            f"{req.generated}"
                        )
                ticks += 1
            return rids, ticks

        rids, ticks = replay(eng)
        summary = {
            "requests": len(trace),
            "ticks": ticks,
            "prefill_traces": {str(k): v for k, v in eng.prefill_trace_counts.items()},
            "decode_traces": eng.decode_trace_count,
            "attention": ops.attention_backend(),
            "decode_kernel": eng.decode_kernel,
        }
        if args.prefill_chunk:
            stats = eng.tick_stats()
            summary["chunk_traces"] = eng.chunk_trace_count
            summary["chunk_launches"] = eng.chunk_launches
            summary["prefill_tokens"] = int(sum(stats["prefill_tokens"]))
            summary["decode_tokens"] = int(sum(stats["decode_tokens"]))
        if eng._spec_on:
            kv = eng.kv_cache_stats()
            summary["speculative"] = {
                "spec_k": args.spec_k,
                "verify_launches": eng.verify_launches,
                "spec_proposed": eng.spec_proposed,
                "spec_accepted": eng.spec_accepted,
                "spec_accept_rate": kv["spec_accept_rate"],
                "spec_rolled_back_pages": kv.get("spec_rolled_back_pages", 0.0),
            }
        if args.paged:
            summary["kv_cache"] = eng.kv_cache_stats()
        if args.kv_dtype != "fp":
            kv = eng.kv_cache_stats()
            summary["quantized_kv"] = {
                "kv_dtype": args.kv_dtype,
                "quantized_pages": kv["quantized_pages"],
                "scale_entries_in_use": kv["scale_entries_in_use"],
                "scale_table_bytes": kv["scale_table_bytes"],
                "dequant_fallbacks": kv["dequant_fallbacks"],
            }
        if (args.oversubscribe > 1.0 or args.chaos_seed is not None
                or args.deadline_ticks is not None or args.cancel):
            statuses = {}
            for rid in rids:
                s = eng._finished[rid].status
                statuses[s] = statuses.get(s, 0) + 1
            kv = eng.kv_cache_stats()
            summary["robustness"] = {
                "oversubscribe": args.oversubscribe,
                "statuses": statuses,
                "preemptions": kv["preemptions"],
                "recompute_tokens": kv["recompute_tokens"],
                "cancelled": kv["cancelled"],
                "deadline_expired": kv["deadline_expired"],
                "numeric_errors": kv["numeric_errors"],
                "rejected_requests": kv["rejected_requests"],
                "health_sweeps": kv["health_sweeps"],
                "chaos_dropped_grants": kv["chaos_dropped_grants"],
                "chaos_events": chaos.events if chaos is not None else [],
            }
        print(json.dumps(summary))
        if args.check_preempts and not eng.kv_cache_stats().get("preemptions"):
            print("check-preempts: the run preempted no request", file=sys.stderr)
            return 1
        if args.check_deterministic:
            # gate: a fresh engine + fresh injector replaying the identical
            # (seed, trace, faults) triple must reproduce every outcome
            chaos2 = make_chaos()
            ref = ServeEngine(cfg, params, ctx=ctx,
                              serve=make_serve(args.spec_k), chaos=chaos2)
            ref_rids, _ = replay(ref, quiet=True)
            for rid, ref_rid in zip(rids, ref_rids):
                a, b = eng._finished[rid], ref._finished[ref_rid]
                if a.status != b.status or a.generated != b.generated:
                    print(
                        f"check-deterministic: rid={rid} run1 "
                        f"({a.status}, {a.generated}) != run2 "
                        f"({b.status}, {b.generated})", file=sys.stderr,
                    )
                    return 1
            if chaos is not None and chaos.events != chaos2.events:
                print(
                    f"check-deterministic: chaos traces diverged:\n"
                    f"  run1 {chaos.events}\n  run2 {chaos2.events}",
                    file=sys.stderr,
                )
                return 1
            print(
                f"check-deterministic: {len(rids)} outcomes and "
                f"{len(chaos.events) if chaos is not None else 0} chaos "
                f"events reproduced exactly"
            )
        if args.check_spec_identical:
            # gate: the speculative run above must be token-identical to a
            # vanilla greedy replay of the exact same trace
            if not eng._spec_on:
                print("check-spec-identical needs --spec-k >= 2", file=sys.stderr)
                return 1
            ref = ServeEngine(cfg, params, ctx=ctx, serve=make_serve(0))
            ref_rids, _ = replay(ref, quiet=True)
            for rid, ref_rid in zip(rids, ref_rids):
                got = eng._finished[rid].generated
                want = ref._finished[ref_rid].generated
                if got != want:
                    print(
                        f"check-spec-identical: rid={rid} speculative stream "
                        f"{got} != vanilla {want}", file=sys.stderr,
                    )
                    return 1
            print(f"check-spec-identical: {len(rids)} streams match vanilla greedy")
        return 0

    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len), dtype=np.int32)
    out = eng.generate(prompts, max_new_tokens=args.new_tokens)
    for i, row in enumerate(out):
        print(f"request {i}: {row.tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
