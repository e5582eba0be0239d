"""``program_spans`` and its two readers on a made-up run: four ticks open in
a one-second window and three end in it, the profile's clock is ahead of the
program's by ``C``, and every expected number is worked out by hand from the
intervals below (program clock, seconds)."""

import importlib.util
import os
import sys

import pytest

from bench import program_spans as P
from bench.trace_reduce import Events, Trace

C = 7.25  # profile clock minus program clock
W0, W1 = 100.0, 101.0

# (name, start, end, parent, args) as repro.spans.take() returns them
SPANS = [
    ("engine.tick", 99.50, 99.60, -1, {}),  # 0: a warm-up tick, before the window
    ("engine.decode.wait", 99.51, 99.56, 0, {}),
    ("engine.tick", 100.0005, 100.010, -1, {}),  # 2: host 9.5 - 6 = 3.5 ms
    ("engine.admit", 100.0005, 100.002, 2, {}),
    ("engine.decode", 100.002, 100.010, 2, {}),
    ("engine.decode.wait", 100.003, 100.009, 4, {}),
    ("engine.decode.post", 100.009, 100.010, 4, {}),
    ("engine.tick", 100.020, 100.050, -1, {}),  # 7: host 30 - 8 - 16 = 6 ms
    ("engine.admit", 100.020, 100.021, 7, {}),
    ("engine.prefill", 100.021, 100.031, 7, {}),
    ("engine.bt_upload", 100.0212, 100.0218, 9, {}),
    ("engine.prefill.wait", 100.022, 100.030, 9, {}),
    ("engine.decode", 100.031, 100.050, 7, {}),
    ("engine.decode.wait", 100.032, 100.048, 12, {}),
    ("engine.decode.post", 100.048, 100.050, 12, {}),
    ("engine.tick", 100.060, 100.100, -1, {}),  # 15: host 40 - 28 = 12 ms
    ("engine.admit", 100.060, 100.070, 15, {}),
    ("engine.decode", 100.070, 100.100, 15, {}),
    ("engine.decode.wait", 100.071, 100.099, 17, {}),
    ("engine.tick", 100.995, 101.005, -1, {}),  # 19: ends after the window
    ("request.queued", 99.90, 100.065, -1, {"rid": 99, "prompt_len": 8}),
]
TICKS = [(99.50, 99.60), (100.0005, 100.010), (100.020, 100.050), (100.060, 100.100),
         (100.995, 101.005)]
# the device: busy in the waits, but the second decode starts 1 ms late
OPS = [(100.003, 100.009), (100.022, 100.030), (100.033, 100.048), (100.071, 100.099)]


def _trace(jitter=(0.0, 0.0, 0.0, 0.0)):
    """The profile: the benchmark's spans and the device ops, moved by ``C``;
    an ``engine_step`` opens 2 us before its tick's stamp, give or take
    ``jitter``."""
    host = [("bench_window", W0 + C + 1e-6, W1 + C),
            ("generator", 100.012 + C, 100.019 + C), ("submit", 100.055 + C, 100.0599 + C)]
    host += [("engine_step", s + C - 2e-6 + j, e + C + 1e-6)
             for (s, e), j in zip(TICKS[1:], jitter)]
    ops = [("fusion", s + C, e + C) for s, e in OPS]
    return Trace((W0 + C + 1e-6, W1 + C), {"TPU:0": Events.of(ops)}, {},
                 Events.of(host))


def _rec(requests=()):
    return {"record": {"window": (W0, W1), "ticks": TICKS, "requests": list(requests)},
            "program_spans": SPANS}


def _reader(name):
    path = os.path.join(os.path.dirname(P.__file__), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_clock_offset_and_mapping():
    off, spread = P.clock_offset(_rec(), _trace(jitter=(0.0, -3e-6, 0.0, 0.0)))
    assert off == pytest.approx(C - 2e-6, abs=1e-9)
    # offsets C - 5, - 2, - 2, - 2 us: quartiles C - 4.25 and C - 2 us
    assert spread == pytest.approx(2.25e-6, abs=1e-9)
    mapped = P.to_trace_clock(_rec(), _trace(), SPANS)
    assert [m[0] for m in mapped] == [s[0] for s in SPANS]
    assert [m[3] for m in mapped] == [s[3] for s in SPANS]
    assert mapped[2][1] == pytest.approx(100.0005 + C - 2e-6, abs=1e-9)
    assert mapped[2][2] - mapped[2][1] == pytest.approx(0.0095, abs=1e-9)


def test_offsets_that_spread_too_far_give_none():
    tr = _trace(jitter=(0.0, 3e-4, 0.0, 0.0))  # one engine_step 300 us off its tick
    assert P.clock_offset(_rec(), tr)[1] == pytest.approx(0.75 * 3e-4, abs=1e-9)
    assert P.to_trace_clock(_rec(), tr, SPANS) is None
    tr = _trace(jitter=(0.0, 1.2e-4, 0.0, 0.0))  # spread 90 us
    assert P.to_trace_clock(_rec(), tr, SPANS) is not None


def test_idle_by_phase():
    tr = _trace()
    got = P.idle_by_phase(tr, P.to_trace_clock(_rec(), tr, SPANS))
    # gaps and what holds each one's middle: 100.000-100.003 admit (the
    # queued request spans it, and is no phase), 100.009-100.022 the
    # generator between ticks, 100.030-100.033 the decode launch before its
    # wait, 100.048-100.071 a submit, 100.099-101.0 nothing
    want = {"no span": 0.901, "submit": 0.023, "generator": 0.013,
            "engine.admit": 0.003, "engine.decode": 0.003}
    assert got == pytest.approx(want, abs=2e-6)
    assert list(got)[:3] == ["no span", "submit", "generator"]
    assert sum(got.values()) == pytest.approx(1.0 - 1e-6 - 0.057, abs=1e-9)


def test_innermost_walks_up_from_a_closed_span():
    # 100.0305: the last span opened before it is the prefill wait (closed),
    # whose parent, the prefill launch, still runs
    assert SPANS[P.innermost(SPANS, 100.0305)][0] == "engine.prefill"
    assert SPANS[P.innermost(SPANS, 100.0215)][0] == "engine.bt_upload"
    assert P.innermost(SPANS, 100.015) == -1


def test_tick_host_ms():
    assert P.tick_host_s(SPANS, (W0, W1)) == pytest.approx([0.0035, 0.006, 0.012], abs=1e-9)
    assert _reader("tick_host_ms")(_rec(), None) == pytest.approx(6.0, abs=1e-6)


def test_admit_wait_p95_ms():
    spans = list(SPANS)
    requests = []
    for rid in range(20):  # waits of 1..20 ms, submitted in the window
        t = 100.1 + 0.01 * rid
        spans.append(("request.queued", t, t + 1e-3 * (rid + 1), -1, {"rid": rid}))
        requests.append({"rid": rid, "submit": t + 1e-5})
    requests.append({"rid": 20, "submit": 100.9})  # never admitted: +inf
    spans.append(("request.queued", 99.0, 99.5, -1, {"rid": 21}))  # before the window
    requests.append({"rid": 21, "submit": 99.0})
    rec = _rec(requests)
    rec["program_spans"] = spans
    # 21 waits; nearest rank ceil(0.95 * 21) = 20: the 20 ms wait, not +inf
    assert _reader("admit_wait_p95_ms.chat")(rec, None) == pytest.approx(20.0, abs=1e-6)


def test_a_program_without_spans_reports_nothing(monkeypatch):
    import repro

    monkeypatch.setitem(sys.modules, "repro.spans", None)  # the import fails
    monkeypatch.delattr(repro, "spans", raising=False)
    rec = _rec([{"rid": 0, "submit": 100.5}])
    del rec["program_spans"]
    assert P.load(rec) is None
    assert _reader("tick_host_ms")(rec, _trace()) is None
    assert _reader("admit_wait_p95_ms.chat")(rec, _trace()) is None
