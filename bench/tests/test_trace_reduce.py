"""``trace_reduce`` on a small trace recorded on the chip: two steps of
context-parallel training (granite-8b widths, 2 layers, seq 32768) on the
2x2 v5e host (TPU v5 lite, 4 chips, one step in the window and the next
dispatched), gzipped.

The pinned numbers are this reduction's readings of that file; the
relations checked beside them hold for any trace."""

import os
import re

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cp32k_train_tp4.xplane.pb.gz")


@pytest.fixture(scope="module")
def tr():
    return T.reduce_trace(DATA)


def test_planes_window_and_spans(tr):
    assert sorted(tr.ops) == ["TPU:0", "TPU:1", "TPU:2", "TPU:3"]
    assert T.window_s(tr) == pytest.approx(4.415071633, abs=1e-9)
    assert {"bench_window", "train_step", "loss_read"} <= set(tr.host.names)
    assert list(T.module_durations(tr, "step_fn")) == pytest.approx([2.203322, 2.20357909],
                                                                     abs=1e-6)


def test_busy_and_idle_share(tr):
    busy = T.busy(tr, "TPU:0")
    assert busy == pytest.approx(4.406898869, abs=1e-6)
    assert 0 < busy <= T.window_s(tr)
    assert T.busy_mean(tr) == pytest.approx(4.4069096465, abs=1e-6)
    idle = 1 - T.busy_mean(tr) / T.window_s(tr)
    assert idle == pytest.approx(0.00185, abs=5e-5)


def test_gaps_are_named_by_the_host_span(tr):
    gaps = T.idle_gaps(tr, "TPU:0", 3)
    assert [g[0] for g in gaps] == ["loss_read", "train_step", "loss_read"]
    assert [g[1] for g in gaps] == pytest.approx([0.00291553, 0.00196523, 0.00181267], abs=1e-7)
    assert sum(g[1] for g in T.idle_gaps(tr, "TPU:0", 10_000)) == pytest.approx(
        T.window_s(tr) - T.busy(tr, "TPU:0"), abs=1e-9)


def test_kernel_time(tr):
    assert T.op_time(tr, r"^mesh_flash_") == pytest.approx(14.559119463, abs=1e-6)
    assert T.op_time(tr, r"^paged_flash_decode") == 0.0
    n_fwd = [sum(1 for n in T.leaves(ev).within(*tr.window).names
                 if n.startswith("mesh_flash_fwd")) for ev in tr.ops.values()]
    assert n_fwd == [32, 32, 32, 32]


def test_exposed_collectives(tr):
    every = T.exposed(tr)
    permute = T.exposed(tr, T.PERMUTE)
    assert every == pytest.approx(0.0634318823, abs=1e-7)
    assert permute == pytest.approx(0.0340808548, abs=1e-7)
    assert 0 < permute < every
    lv = T.leaves(tr.ops["TPU:0"]).within(*tr.window)
    total = sum(e - s for n, s, e in zip(lv.names, lv.start, lv.end)
                if re.match(T.COLLECTIVE, n))
    assert T.exposed(tr) <= max(total, every)  # exposed is a part of the total
