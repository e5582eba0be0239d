"""Readings that set the limits of ``correct`` (run on the chip, by hand).

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--control-seeds 1,2,3]
        [--seconds 10] [--out control.json]

For each seed of ``--seeds`` it drives the cell as a run does (set-up and a
window of ``--seconds`` at the cell's own load) and reads the number
``correct`` compares against the reference: the program's readings, whose
largest is the lower end of the limit.  For each seed of ``--control-seeds``
it reads the control, the float8 reference put in the program's place, and
a served token altered; their smallest is the upper end.  Everything runs
in one process.  Writes one JSON object per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import run

    bench = run.load_json("BENCHMARK.json")
    wl, cfg_json, traffic = run.cell(bench, args.workload)
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in sorted(set(seeds) | ctl):
        t0 = time.perf_counter()
        row = serve_readings(cfg_json, traffic, seed, args.seconds, seed in ctl)
        row.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    return 0


def serve_readings(cfg_json, traffic, seed, seconds, control):
    from bench import run
    from bench.serve_cell import ServeCell, served_gaps

    sc = ServeCell(cfg_json, traffic, seed, seconds)
    sc.warm_shapes()
    source = run.generator(traffic["generator"]).make(traffic, seed, seconds,
                                                      sc.cfg.vocab_size)
    record = sc.run(source)
    sample = sc.check_sample(record)
    sc.free()
    gap, n, ctl = served_gaps(cfg_json, seed, sample, control=control)
    row = {"served_logit_gap": gap, "tokens": n, "requests": len(sample)}
    if ctl:
        row.update(ctl)
    return row


if __name__ == "__main__":
    sys.exit(main())
