"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file and
a traffic file; ``serve_cell`` drives the program's serving engine with
it.  In order: name the device (no TPU, too
few chips or a device kind missing from ``peaks.json`` exits 1 with no
result); turn on JAX's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
when set, else the checkout's ``.jax_cache``); make the weights from ``--seed`` on the device; warm the
cell's shapes and its traffic; measure for ``--seconds``; check the outputs
against the plain reference (``reference.py``) and print the result.  With
``--trace 1`` the window runs under the profiler and the result carries the
per-layer metrics, read by ``metrics/<name>.py`` (or, where no such file
exists, ``metrics/<base>.py`` for a name ``<base>.<cell suffix>``) from the
run's record and the reduced trace; with ``--trace 0`` it carries the end-to-end metrics.

Earlier lines say how many programs compiled inside the window (should be
0), how late the load generator ran and the peak HBM per device.  The last
lines on standard error, and the result's last key ``checks``, give every
number ``correct`` compares beside its limit (``limits/<workload>.json``).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def _process_start() -> float:
    """``time.perf_counter()`` at this process's start (Linux), else now."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - (uptime - started / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell(bench: dict, workload: str):
    """(workload entry, configuration dict, traffic dict) of a cell."""
    wl = {w["name"]: w for w in bench["workloads"]}.get(workload)
    if wl is None:
        raise SystemExit(f"unknown workload {workload!r}")
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    return wl, load_json(conf["file"]), load_json("bench", "traffic", wl["traffic"] + ".json")


def reader(name: str):
    """``read`` of ``metrics/<name>.py``, else of the file of the name before
    its last dot (one reader serves ``idle_share.chat`` and ``idle_share.code``)."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, "metrics", name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, workload: str, trace: bool):
    """The cell's end-to-end metrics (``trace`` off) or per-layer ones."""
    if not trace:
        return [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    shown = {m["name"] for m in metrics_for(bench, workload, False)}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in shown else [])]


def generator(name: str):
    return importlib.import_module("bench.traffic." + name)


def peak_memory(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def main(argv=None, *, require_tpu=True, adjust=None, plant=None) -> int:
    """``require_tpu``, ``adjust`` (shrinks the configuration and traffic
    dicts in place) and ``plant`` (breaks the timed path) exist for the
    benchmark's own tests on the CPU."""
    t_start = _process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]
    bench = load_json("BENCHMARK.json")
    wl, cfg_json, traffic = cell(bench, args.workload)
    if adjust is not None:
        adjust(cfg_json, traffic)

    import jax

    devices = jax.devices()[: wl["chips"]]
    dev = devices[0]
    peaks = load_json("bench", "peaks.json")["devices"]
    if require_tpu:
        if dev.platform != "tpu":
            print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
            return 1
        if len(jax.devices()) < wl["chips"]:
            print(f"the cell needs {wl['chips']} chips, JAX found {len(jax.devices())}",
                  file=sys.stderr)
            return 1
        if dev.device_kind not in peaks:
            print(f"device kind {dev.device_kind!r} is not in bench/peaks.json", file=sys.stderr)
            return 1
        peak = peaks[dev.device_kind]
    else:
        peak = next(iter(peaks.values()))

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    from bench.compile_clock import CompileClock
    from bench.reference import Arch

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache_dir}")
    if require_tpu and ops.attention_backend() != "pallas":
        print(f"kernels resolve to {ops.attention_backend()}, not compiled Pallas",
              file=sys.stderr)
        return 1
    limits = load_json("bench", "limits", args.workload + ".json")
    rec = {"cfg_json": cfg_json, "traffic": traffic, "arch": Arch.from_config(cfg_json),
           "chips": wl["chips"], "peak_flops": peak["bf16_flops_per_s"],
           "peak_bw": peak["hbm_bytes_per_s"]}

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        record, checks, attempted, failed, lines = run_serve(args, cfg_json, traffic, clock,
                                                             trace_dir, plant)
        rec["record"] = record
        rec["setup_s"] = record["window"][0] - t_start
        rec.update(record.get("extra", {}))
        tr = None
        if trace_dir is not None:
            from bench.trace_reduce import find_xplane, reduce_trace

            tr = reduce_trace(find_xplane(trace_dir))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    for line in lines:
        print(line)
    print(f"compiles in the window: {record['compiles_in_window']} "
          f"(set-up compiled {record['compile_s_setup']:.1f} s, "
          f"{clock.hits} persistent-cache hits in all)")
    print(f"peak HBM per device: {record['memory_peak_bytes']} bytes")

    metrics = {}
    for m in metrics_for(bench, args.workload, bool(args.trace)):
        val = reader(m["name"])(rec, tr)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": None, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if tr is not None:
        from bench import trace_reduce as T

        first = sorted(tr.ops)[0]
        device["busy_s"] = T.busy_mean(tr)
        device["window_s"] = T.window_s(tr)
        result["breakdown"] = {"device_ops": T.top_ops(tr, first),
                               "idle_gaps": T.idle_gaps(tr, first)}
    ok = failed == 0
    shown = {}
    for name, value in checks.items():
        limit = limits[name]["limit"]
        ok = ok and value <= limit
        shown[name] = {"value": value, "limit": limit}
    result["correct"] = bool(ok)
    result["checks"] = shown
    for name, c in shown.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result))
    return 0


def run_serve(args, cfg_json, traffic, clock, trace_dir, plant):
    import jax
    import numpy as np

    from bench.serve_cell import ServeCell, served_gaps
    from bench.stats import measured

    c0, t0 = clock.seconds, time.perf_counter()
    sc = ServeCell(cfg_json, traffic, args.seed, args.seconds)
    t1 = time.perf_counter()
    if plant is not None:
        plant(sc)
    expect = cfg_json.get("expect", {})
    if "decode_kernel" in expect and sc.engine.decode_kernel != expect["decode_kernel"] \
            and jax.default_backend() == "tpu":
        raise SystemExit(f"decode kernel resolved to {sc.engine.decode_kernel}")
    shapes = sc.warm_shapes()
    t2 = time.perf_counter()
    source = generator(traffic["generator"]).make(traffic, args.seed, args.seconds,
                                                  sc.cfg.vocab_size)
    before = clock.compiles + clock.hits
    setup_compile = clock.seconds - c0
    record = sc.run(source, trace_dir=trace_dir)
    # compiles in the window: counted up to the close (the tail is outside)
    record["compiles_in_window"] = clock.compiles + clock.hits - before
    record["compile_s_setup"] = setup_compile
    record["memory_peak_bytes"] = peak_memory(jax.devices()[: cfg_json["chips"]])
    record["extra"] = {"page_size": sc.engine.allocator.layout.page_size}
    meas = measured(record)
    lag = np.array([r["submit"] - r["due"] for r in meas]) * 1e3
    lines = [f"set-up: weights and engine {t1 - t0:.1f} s, {len(shapes)} prefill shapes "
             f"(k prompts, bucket) and the decode step warmed in {t2 - t1:.1f} s, traffic "
             f"warm-up {record['window'][0] - record['t_zero']:.1f} s",
             f"requests due in the window: {len(meas)}; generator lag (submit - due, "
             f"includes waiting for the current tick) p50 {np.median(lag):.1f} ms, "
             f"p95 {np.percentile(lag, 95):.1f} ms, max {lag.max():.1f} ms"
             if len(lag) else "requests due in the window: 0"]
    sample = sc.check_sample(record)
    sc.free()
    unfinished = sum(1 for r in meas if r["status"] not in ("ok", "in_flight"))
    gap, n, _ = served_gaps(cfg_json, args.seed, sample)
    lines.append(f"reference: {len(sample)} requests, {n} served tokens compared")
    checks = {"served_logit_gap": gap}
    return record, checks, len(meas), unfinished, lines


if __name__ == "__main__":
    sys.exit(main())
