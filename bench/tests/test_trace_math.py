"""The interval arithmetic of ``trace_reduce`` on a hand-made trace."""

import numpy as np

from bench import trace_reduce as T


def ev(items):
    return T.Events.of(items)


def hand_trace():
    # window 0..10 s; device A: fusion 1-3, a permute 2.5-4 (0.5 s overlaps
    # compute, 1 s exposed), all-gather 6-7 (exposed); device B idle 0-5
    ops = {
        "TPU:0": ev([("fusion.1", 1.0, 3.0), ("collective-permute-done.2", 2.5, 4.0),
                     ("all-gather-start.3", 6.0, 7.0), ("mesh_flash_fwd.4", 8.0, 9.0)]),
        "TPU:1": ev([("fusion.1", 5.0, 10.0)]),
    }
    modules = {"TPU:0": ev([("jit_step_fn(1)", 1.0, 9.0)]),
               "TPU:1": ev([("jit_step_fn(1)", 5.0, 10.0)])}
    host = ev([("bench_window", 0.0, 10.0), ("train_step", 0.0, 0.9),
               ("loss_read", 4.0, 8.0), ("train_step", 4.5, 5.5)])
    return T.Trace((0.0, 10.0), ops, modules, host)


def test_union_and_subtract():
    iv = T.union(np.array([1.0, 2.0, 5.0]), np.array([3.0, 4.0, 6.0]))
    assert iv == [(1.0, 4.0), (5.0, 6.0)]
    assert T.subtract([(0.0, 10.0)], [(1.0, 2.0), (3.0, 4.0)]) == 8.0
    assert T.subtract([(2.5, 4.0)], [(1.0, 3.0)]) == 1.0


def test_busy_idle_and_gaps():
    tr = hand_trace()
    assert T.busy(tr, "TPU:0") == 2.0 + 1.0 + 1.0 + 1.0  # 1-4, 6-7, 8-9
    assert T.busy(tr, "TPU:1") == 5.0
    assert T.busy_mean(tr) == 5.0
    gaps = T.idle_gaps(tr, "TPU:0")
    # gaps: 0-1 (train_step covers 0.45), 4-6 (mid 5: train_step inside
    # loss_read, the innermost wins), 7-8 (loss_read), 9-10 (no span)
    assert gaps[0] == ["train_step", 2.0]
    assert sorted(gaps[1:]) == [["loss_read", 1.0], ["no host span", 1.0],
                                ["train_step", 1.0]]


def test_leaves_drop_containers():
    e = ev([("while.1", 0.0, 10.0), ("fusion.2", 1.0, 2.0), ("fusion.3", 3.0, 4.0),
            ("copy.4", 11.0, 12.0)])
    assert T.leaves(e).names == ["fusion.2", "fusion.3", "copy.4"]
    assert T.op_name("%fusion.12 = bf16[8]{0} fusion(%p)") == "fusion.12"
    assert T.op_name("jit_step_fn(123)") == "jit_step_fn"


def test_exposed_collectives_and_kernel_time():
    tr = hand_trace()
    # device 0: permute 1.0 s exposed + all-gather 1.0 s; device 1: none
    assert T.exposed(tr) == (2.0 + 0.0) / 2
    assert T.exposed(tr, T.PERMUTE) == (1.0 + 0.0) / 2
    assert T.op_time(tr, r"mesh_flash_") == 1.0
    assert list(T.module_durations(tr, "step_fn")) == [8.0]
    assert T.top_ops(tr, "TPU:0")[0] == ["fusion", 2.0]
