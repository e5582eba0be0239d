"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).  Multi-pod: 2 pods x 256 =
512 chips as (pod=2, data=16, model=16) — the pod axis is pure data
parallelism across DCN.  Below a pod (``launch_context``), the sequence
axis ``model`` covers every device: a 2x2 v5e host runs 4-way context
parallelism.  A FUNCTION (not a module constant) so importing
never touches jax device state; the dry-run forces 512 host devices before
any jax import (see launch/dryrun.py).
"""

from __future__ import annotations

from repro.compat import make_mesh

__all__ = ["make_production_mesh", "make_context", "launch_context"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_context(mesh=None, *, multi_pod: bool = False, **kw):
    """ParallelCtx wired to the production axis roles."""
    from repro.parallel.context import ParallelCtx

    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    batch_axes = ("pod", "data") if "pod" in mesh.shape else ("data",)
    return ParallelCtx(mesh=mesh, batch_axes=batch_axes, sp_axis="model", **kw)


def launch_context(n: int, *, multi_pod: bool = False, **kw):
    """ParallelCtx the launchers run under on ``n`` devices: no mesh on one
    device, the production mesh from 256 on, and otherwise a mesh whose
    sequence axis spans all ``n`` devices (``multi_pod`` puts half of them
    on each of two pods)."""
    from repro.parallel.context import ParallelCtx

    if n >= 256:
        return make_context(multi_pod=multi_pod and n >= 512, **kw)
    if n == 1:
        return ParallelCtx(**kw)
    if multi_pod:
        return make_context(make_mesh((2, 1, n // 2), ("pod", "data", "model")), **kw)
    return make_context(make_mesh((1, n), ("data", "model")), **kw)
