"""Find the chat cell's knee on the chip: the highest open-loop rate at which
p95 TTFT stays within ``--ttft-limit`` and p95 inter-token gap within
``--itl-limit`` with no growing backlog.

    python3 bench/knee_sweep.py --workload granite-serve-chat \\
        --rates 6,8,10,12,14 --seconds 30 --seed 7 [--out knee.json]

One process: the engine is built and warmed once, then each rate plays its
own traffic (the traffic file's mix at that rate, warm-up included) and the
engine drains before the next.  A backlog grows when the requests due in
the window's last third wait, at the median, more than twice as long for
their first token as those of the first third.  Set the traffic file's
``rate_per_s`` to 0.8 x the knee this prints, and record the sweep in
PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="granite-serve-chat")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--ttft-limit", type=float, default=2.0)
    ap.add_argument("--itl-limit", type=float, default=0.25)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from bench import run
    from bench.serve_cell import ServeCell
    from bench.stats import inter_token_gaps, measured, nearest_rank, ttfts
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _, cfg_json, traffic = run.cell(run.load_json("BENCHMARK.json"), args.workload)
    sc = ServeCell(cfg_json, traffic, args.seed, args.seconds)
    sc.warm_shapes()
    rows, knee = [], None
    for rate in [float(r) for r in args.rates.split(",")]:
        spec = dict(traffic, rate_per_s=rate)
        source = run.generator(spec["generator"]).make(spec, args.seed, args.seconds,
                                                       sc.cfg.vocab_size)
        sc.tracked = {}
        record = sc.run(source)
        while sc.engine.has_work:
            sc._step()
        t = np.array(ttfts(record))
        due = np.array([r["due"] for r in measured(record)])
        w0, w1 = record["window"]
        third = (w1 - w0) / 3
        first = np.median(t[due < w0 + third]) if np.any(due < w0 + third) else np.nan
        last = np.median(t[due >= w1 - third]) if np.any(due >= w1 - third) else np.nan
        gaps = inter_token_gaps(record)
        row = {"rate": rate, "requests": len(t),
               "ttft_p50_ms": 1e3 * nearest_rank(t, 0.5), "ttft_p95_ms": 1e3 * nearest_rank(t, 0.95),
               "itl_p50_ms": 1e3 * nearest_rank(gaps, 0.5), "itl_p95_ms": 1e3 * nearest_rank(gaps, 0.95),
               "ttft_first_third_p50_ms": 1e3 * first, "ttft_last_third_p50_ms": 1e3 * last,
               "unfinished": int(np.sum(~np.isfinite(t)))}
        row["within"] = bool(row["ttft_p95_ms"] <= 1e3 * args.ttft_limit
                             and row["itl_p95_ms"] <= 1e3 * args.itl_limit
                             and not last > 2 * first)
        if row["within"]:
            knee = rate
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(json.dumps({"knee_rate_per_s": knee,
                      "cell_rate_per_s": None if knee is None else 0.8 * knee}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "knee_rate_per_s": knee}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
