"""Runtime parallelism context threaded through the model code.

``ParallelCtx`` carries the mesh handle and the axis roles; model layers use
it to (a) place sharding constraints on activations, (b) wrap attention in
``shard_map`` over the sequence-parallel axis with the configured
Mesh-Attention tile/schedule, and (c) pick MoE/SSM distribution modes.
``ParallelCtx()`` (no mesh) is the single-device mode used by smoke tests.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ParallelCtx"]


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    mesh: Optional[Mesh] = None
    batch_axes: Tuple[str, ...] = ()  # e.g. ("pod", "data")
    sp_axis: Optional[str] = None  # sequence-parallel axis (e.g. "model")
    # --- Mesh-Attention configuration (the paper's knobs) ---
    attn_impl: str = "mesh"  # any registered dispatch backend (mesh | ring | ulysses | ...)
    mesh_a: Optional[int] = None  # tile height; None -> divisor closest to sqrt(n)
    allow_concurrent_rings: bool = False
    bwd_wire: str = "qdod"
    comm_overlap: str = "overlap"  # ring transport: serial (permutes barriered
    # ahead of the blocks) | overlap (in flight during them, default) | bidir
    # (half-payload ppermute pairs over both ring directions); bitwise-equal
    block_q: int = 128
    block_kv: int = 128
    attn_autotune: bool = False  # pick (a, b) + schedules via the simulator
    # (Figure 6) through the on-disk plan cache instead of the sqrt-n heuristic
    plan_cache_dir: Optional[str] = None  # None -> dispatch's default cache dir
    decode_kernel: str = "auto"  # flash-decode variant: auto (paged -> the
    # native paged kernel where Pallas runs, else the gather/band
    # reference) | native | gather
    # --- other knobs ---
    remat: bool = True
    unroll_layers: bool = False  # python-loop the layer stack (dry-run cost
    # extrapolation: XLA cost_analysis counts a while-loop body once)
    param_dtype: object = None  # set by launcher (jnp dtype); None -> float32
    # --- beyond-paper optimizations (EXPERIMENTS.md §Perf) ---
    grads_rs: bool = False  # constrain grads to the param sharding so XLA
    # emits reduce-scatters instead of all-reduce-to-replicated
    mla_latent_wire: bool = False  # MLA: circulate the 288-wide latent on the
    # KV ring instead of 2*H*dk decompressed heads (forward-only paths)

    @property
    def sp_size(self) -> int:
        if self.mesh is None or self.sp_axis is None:
            return 1
        return self.mesh.shape[self.sp_axis]

    @property
    def batch_spec(self):
        return tuple(self.batch_axes) if self.batch_axes else None

    def eff_batch_axes(self, b: int):
        """Largest-product subset of batch_axes whose sizes' product divides
        b (e.g. long_500k's global_batch=1 leaves the data axis idle)."""
        if self.mesh is None or not self.batch_axes:
            return ()
        axes = list(self.batch_axes)
        best: tuple = ()
        best_prod = 1
        for mask in range(1, 1 << len(axes)):
            sub = tuple(a for i, a in enumerate(axes) if mask >> i & 1)
            prod = 1
            for a in sub:
                prod *= self.mesh.shape[a]
            if b % prod == 0 and prod > best_prod:
                best, best_prod = sub, prod
        return best

    def eff_batch_spec(self, b: int):
        sub = self.eff_batch_axes(b)
        return sub if sub else None

    def act_spec(self, *dims, batch: Optional[int] = None):
        """PartitionSpec for activations: first dim batch, rest as given
        ('seq' -> sp_axis, None otherwise)."""
        parts = [self.batch_spec if batch is None else self.eff_batch_spec(batch)]
        for d in dims:
            parts.append(self.sp_axis if d == "seq" else None)
        return P(*parts)

    def constrain(self, x, *dims):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, self.act_spec(*dims, batch=x.shape[0]))
        )

    def tile_a(self) -> int:
        from repro.core.tiling import best_square_a

        if self.mesh_a is not None:
            return self.mesh_a
        return best_square_a(self.sp_size)

    def shard_map_mesh(self):
        """Mesh to hand to nested shard_map calls: when tracing already
        happens under a mesh context (e.g. inside a partial-manual
        shard_map over the pod axis), the AMBIENT abstract mesh must be
        used — its axis_types carry which axes are already manual."""
        am = jax.sharding.get_abstract_mesh()
        if am.shape_tuple:
            return am
        return self.mesh
