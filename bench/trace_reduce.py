"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

``reduce_trace(path)`` reads the file with ``jax.profiler.ProfileData`` and
keeps, for each accelerator plane (``/device:TPU:n``), the events of its op
line (``XLA Ops``) and its program line (``XLA Modules``), and from the host
planes the benchmark's own annotations (``bench_window`` and the spans
inside it).  Times are seconds on the trace's clock.  The functions below it
compute busy time, idle gaps and who the host was serving in them, kernel
time and collective time left exposed; every per-layer metric is built from
them, so every run computes them the same way.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("bench_window", "engine_step", "generator", "submit", "train_step", "loss_read")
COLLECTIVE = re.compile(r"^(collective-permute|all-gather|reduce-scatter|all-reduce|all-to-all)")
PERMUTE = re.compile(r"^collective-permute")


@dataclass
class Events:
    names: List[str] = field(default_factory=list)
    start: np.ndarray = field(default_factory=lambda: np.zeros(0))
    end: np.ndarray = field(default_factory=lambda: np.zeros(0))

    @classmethod
    def of(cls, items):
        items = sorted(items, key=lambda e: e[1])
        return cls([e[0] for e in items], np.array([e[1] for e in items], float),
                   np.array([e[2] for e in items], float))

    def select(self, pattern) -> "Events":
        keep = [i for i, n in enumerate(self.names) if pattern.search(n)]
        return Events([self.names[i] for i in keep], self.start[keep], self.end[keep])

    def within(self, t0, t1) -> "Events":
        keep = np.nonzero((self.start >= t0) & (self.end <= t1))[0]
        return Events([self.names[i] for i in keep], self.start[keep], self.end[keep])


@dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[str, Events]  # device plane -> op events
    modules: Dict[str, Events]  # device plane -> program events
    host: Events  # the benchmark's host spans


def trace_options():
    """Profiler options of a traced run: no Python function tracing (it
    would slow the host it measures), host annotations kept."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(path: str) -> Trace:
    """The trace at ``path`` (an ``.xplane.pb``, or one gzipped)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = plane.name.split(":", 1)[1]
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    ev = [(op_name(e.name), e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]
                    (ops if line.name == OPS_LINE else modules)[dev] = Events.of(ev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9))
    host = Events.of(host)
    win = [i for i, n in enumerate(host.names) if n == "bench_window"]
    if not win:
        raise ValueError("the trace holds no bench_window span")
    window = (float(host.start[win[0]]), float(host.end[win[0]]))
    if not ops:
        raise ValueError("the trace holds no accelerator op line")
    return Trace(window, ops, modules, host)


def op_name(text: str) -> str:
    """The HLO instruction's name: an op event is named by its whole HLO
    text (``%fusion.12 = bf16[...] fusion(...)``); a program by its name."""
    m = re.match(r"%?([\w.\-]+)", text)
    return m.group(1) if m else text


def leaves(ev: "Events") -> "Events":
    """The events that contain no other event: a ``while`` op spans the ops
    of its body on the same line, and would hide them from any sum."""
    parent = np.zeros(len(ev.names), bool)
    open_ends: List[Tuple[float, int]] = []
    for i in range(len(ev.names)):
        while open_ends and open_ends[-1][0] <= ev.start[i]:
            open_ends.pop()
        if open_ends and ev.end[i] <= open_ends[-1][0]:
            parent[open_ends[-1][1]] = True
        open_ends.append((ev.end[i], i))
    keep = np.nonzero(~parent)[0]
    return Events([ev.names[i] for i in keep], ev.start[keep], ev.end[keep])


def union(start: np.ndarray, end: np.ndarray) -> List[Tuple[float, float]]:
    """Merged intervals of the given ones."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(zip(start.tolist(), end.tolist())):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def measure(iv) -> float:
    return float(sum(e - s for s, e in iv))


def clip(iv, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in iv if e > t0 and s < t1]


def subtract(a, b):
    """Measure of intervals ``a`` minus the parts ``b`` covers (both merged)."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def busy(tr: Trace, dev: str) -> float:
    """Seconds of the window in which some op ran on ``dev``."""
    ev = tr.ops[dev]
    return measure(clip(union(ev.start, ev.end), *tr.window))


def busy_mean(tr: Trace) -> float:
    return float(np.mean([busy(tr, d) for d in tr.ops]))


def window_s(tr: Trace) -> float:
    return tr.window[1] - tr.window[0]


def idle_gaps(tr: Trace, dev: str, top: int = 10):
    """The longest idle gaps of ``dev`` in the window, each named by the
    innermost benchmark span the host was in at the gap's middle."""
    iv = clip(union(tr.ops[dev].start, tr.ops[dev].end), *tr.window)
    edges = [tr.window[0]] + [x for s, e in iv for x in (s, e)] + [tr.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    h = tr.host
    out = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        inside = [i for i in range(len(h.names))
                  if h.start[i] <= mid <= h.end[i] and h.names[i] != "bench_window"]
        name = min(inside, key=lambda i: h.end[i] - h.start[i]) if inside else None
        out.append([h.names[name] if name is not None else "no host span", e - s])
    return out


def op_key(name: str) -> str:
    """An op's name without its instance number (``fusion.12`` -> ``fusion``)."""
    return re.sub(r"[.:]\d+$", "", name)


def top_ops(tr: Trace, dev: str, top: int = 10):
    """Device seconds by op name (leaf ops) in the window, largest first."""
    ev = leaves(tr.ops[dev]).within(*tr.window)
    acc: Dict[str, float] = {}
    for n, s, e in zip(ev.names, ev.start, ev.end):
        acc[op_key(n)] = acc.get(op_key(n), 0.0) + (e - s)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def op_time(tr: Trace, pattern: str) -> float:
    """Device seconds, summed over devices, of leaf ops whose name
    matches."""
    rx = re.compile(pattern)
    total = 0.0
    for ev in tr.ops.values():
        sel = leaves(ev).select(rx).within(*tr.window)
        total += float(np.sum(sel.end - sel.start))
    return total


def module_durations(tr: Trace, pattern: str, dev: str = None) -> np.ndarray:
    """Durations of the programs whose name matches, on ``dev`` (default:
    the first device), in the window."""
    dev = dev or sorted(tr.modules)[0]
    ev = tr.modules[dev].select(re.compile(pattern)).within(*tr.window)
    return ev.end - ev.start


def exposed(tr: Trace, pattern=COLLECTIVE) -> float:
    """Seconds per device, averaged, in which a leaf op matching
    ``pattern`` ran and no other leaf op did."""
    vals = []
    for ev in tr.ops.values():
        ev = leaves(ev).within(*tr.window)
        hit = np.array([bool(pattern.search(n)) for n in ev.names], bool)
        if not hit.any():
            vals.append(0.0)
            continue
        coll = union(ev.start[hit], ev.end[hit])
        rest = union(ev.start[~hit], ev.end[~hit])
        vals.append(subtract(coll, rest))
    return float(np.mean(vals))
