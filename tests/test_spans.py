"""Host spans inside ``ServeEngine`` (``repro.spans``).

Off: a run with no profiler session keeps nothing.  On: under
``jax.profiler.trace`` every ``step()`` is one ``engine.tick`` whose phases lie
inside it, every ``.wait`` lies inside its launch, the ticks' token counters
equal ``tick_stats()``, every request has one ``request.queued`` span, and the
profile itself holds the same spans with their args as event stats.  One case
per ingestion path: packed one-shot prefill, continuous (chunked) prefill and
speculative decode."""

import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs import get_config
from repro.models import transformer as tfm
from repro.serve.config import ServeConfig
from repro.serve.engine import ServeEngine

PHASES = {"engine.admit", "engine.prefill", "engine.chunk", "engine.draft", "engine.pages",
          "engine.bt_upload", "engine.decode", "engine.verify", "engine.health"}
TICK_ARGS = {"tick", "admitted", "decodable", "prefill_tokens", "decode_tokens",
             "prefill_launches", "pages_allocated", "cow_copies", "prefix_hit_pages",
             "bt_uploads", "preemptions", "finished", "retraced"}
MODES = {
    "packed": dict(prefill_buckets=(32, 64)),
    "chunk": dict(prefill_chunk=8),
    "spec": dict(prefill_buckets=(32, 64), spec_k=4, spec_max_misses=None),
}
LAUNCH = {"packed": "engine.prefill", "chunk": "engine.chunk", "spec": "engine.verify"}


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-8b").reduced()
    return cfg, tfm.init_params(cfg, jax.random.PRNGKey(7))


def _serve(granite, mode, ticks=10):
    """Three requests on two slots (one waits in the queue), ``ticks`` steps."""
    cfg, params = granite
    eng = ServeEngine(cfg, params, serve=ServeConfig(
        max_seq=64, num_slots=2, paged=True, page_size=4, health_every=4, **MODES[mode]))
    # repeating prompts: the n-gram drafter finds continuations to verify
    for n, new in ((12, 6), (20, 5), (9, 6)):
        eng.submit(np.tile(np.arange(3, 7, dtype=np.int32), 6)[:n], max_new_tokens=new)
    for _ in range(ticks):
        eng.step()
    return eng


def test_no_session_keeps_nothing(granite):
    spans.take()
    eng = _serve(granite, "packed")
    assert eng.tick_stats()["ticks"] == 10
    assert spans.take() == []


def _inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


@pytest.mark.parametrize("mode", sorted(MODES))
def test_spans_under_a_profiler_session(granite, mode, tmp_path):
    spans.take()
    with jax.profiler.trace(str(tmp_path)):
        eng = _serve(granite, mode)
    got = spans.take()
    assert got.dropped == 0 and None not in got
    names = Counter(s.name for s in got)

    ticks = [s for s in got if s.name == "engine.tick"]
    assert len(ticks) == 10 and all(s.parent == -1 for s in ticks)
    assert [t.args["tick"] for t in ticks] == list(range(10))
    assert all(set(t.args) == TICK_ARGS for t in ticks)
    stats = eng.tick_stats()
    assert [t.args["prefill_tokens"] for t in ticks] == stats["prefill_tokens"]
    assert [t.args["decode_tokens"] for t in ticks] == stats["decode_tokens"]
    assert sum(t.args["retraced"] for t in ticks) == (
        eng.decode_trace_count + eng.chunk_trace_count + eng.verify_trace_count
        + sum(eng.prefill_trace_counts.values()))
    assert sum(t.args["bt_uploads"] for t in ticks) == eng.bt_uploads
    assert sum(t.args["pages_allocated"] for t in ticks) == eng.allocator.fresh_allocs

    for s in got:
        if s.name in PHASES - {"engine.bt_upload"}:
            assert got[s.parent].name == "engine.tick", s
        if s.name.endswith((".wait", ".post")):
            assert got[s.parent].name == s.name.rsplit(".", 1)[0], s
        if s.parent >= 0:
            assert _inside(s, got[s.parent]), s
    assert names["engine.admit"] == 10 and names["engine.health"] == 2
    assert names[LAUNCH[mode]] >= 1
    for launch in ("engine.prefill", "engine.chunk", "engine.decode", "engine.verify"):
        assert names[launch + ".wait"] == names[launch]
    launches = eng.prefill_launches + eng.chunk_launches
    assert names["engine.prefill"] + names["engine.chunk"] == launches
    assert names["engine.verify"] == eng.verify_launches

    queued = [s for s in got if s.name == "request.queued"]
    assert sorted(s.args["rid"] for s in queued) == [0, 1, 2]
    assert all(s.start <= s.end and s.parent == -1 for s in queued)
    assert {s.args["rid"]: s.args["prompt_len"] for s in queued} == {0: 12, 1: 20, 2: 9}

    # the profile holds the same spans, the args as event stats
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    events = [e for plane in ProfileData.from_file(path[0]).planes
              if plane.name.startswith("/host:") for line in plane.lines for e in line.events]
    in_profile = Counter(e.name for e in events if e.name.startswith(("engine.", "request.")))
    assert in_profile == names
    tick_stats = sorted((dict(e.stats) for e in events if e.name == "engine.tick"),
                        key=lambda a: a["tick"])
    assert tick_stats == [t.args for t in ticks]
    waits = {dict(e.stats)["rid"]: dict(e.stats) for e in events if e.name == "request.queued"}
    assert sorted(waits) == [0, 1, 2] and all(w["wait_ms"] >= 0 for w in waits.values())
    # a profile stat reads a one-rid string back as a number
    prefills = [{k: str(v) for k, v in e.stats} for e in events if e.name == "engine.prefill"]
    assert prefills == [{k: str(v) for k, v in s.args.items()}
                        for s in got if s.name == "engine.prefill"]


def test_parents_cap_and_late_args(tmp_path, monkeypatch):
    spans.take()
    monkeypatch.setattr(spans, "CAP", 4)
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("a", x=1) as a:
            assert a.recording
            with spans.span("b"):
                with spans.span("c"):
                    pass
            a.set(y=2)
        with spans.span("d"):
            with spans.span("e"):  # past the cap: dropped, its parent kept
                pass
    got = spans.take()
    assert [(s.name, s.parent) for s in got] == [("a", -1), ("b", 0), ("c", 1), ("d", -1)]
    assert got.dropped == 1 and got[0].args == {"x": 1, "y": 2}
    assert _inside(got[2], got[1]) and _inside(got[1], got[0])
    with spans.span("f") as f:  # no session: the shared span that keeps nothing
        assert not f.recording and spans.span("g") is f
    assert spans.now() is None and spans.take() == []
