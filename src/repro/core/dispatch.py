"""Unified distributed-attention dispatch: "which attention" is a config.

The paper's pitch is that Mesh-Attention *generalizes* the existing
distributed-attention family — Ring-Attention is the (a=1, b=n) tile, DS-
Ulysses the head-parallel alternative, flash-decode the inference analogue —
so the repo routes every attention call through ONE seam:

    distributed_attention(q, k, v, cfg=plan, ctx=ctx)

``AttentionPlanConfig`` names a backend from the **registry** (``mesh``,
``ring``, ``ulysses``, ``decode``, ``local-flash``) plus the tile/mask/block
knobs; ``plan_from_ctx`` derives one from a ``ParallelCtx`` the way the model
layers used to hand-wire it.  When ``autotune=True`` the (a, b) tile and the
greedy comm/compute schedules come from the Figure-6 flow
(``autotune.plan_for`` / ``autotune.tune`` over the event simulator), with an
on-disk **plan cache** keyed by (shape, dtype, n, hardware profile) so
repeated serve/train launches skip re-tuning.

Layering: this module may import every backend under ``core/`` and the
``compat`` shim; nothing outside ``core/`` (and tests) imports backends
directly anymore.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import autotune
from repro.core import kv_quant
from repro.core import schedule as S
from repro.core.am import CommModel
from repro.core.decode_attention import (
    paged_cache_chunk_decode,
    paged_cache_chunk_update,
    paged_cache_decode,
    paged_cache_update,
    sharded_cache_chunk_decode,
    sharded_cache_chunk_update,
    sharded_cache_decode,
    sharded_cache_update,
)
from repro.core.masking import MaskSpec
from repro.core.mesh_attention import MeshAttentionConfig, mesh_attention, mesh_attention_wire
from repro.core.simulator import HardwareModel
from repro.core.tiling import best_square_a, stripe_permutation
from repro.core.ulysses import ulysses_attention
from repro.kernels import ops

__all__ = [
    "AttentionPlanConfig",
    "Backend",
    "register_backend",
    "get_backend",
    "available_backends",
    "resolve_backend_name",
    "distributed_attention",
    "attention_in_shard_map",
    "decode_attention_step",
    "chunk_attention_step",
    "latent_wire_attention",
    "plan_from_ctx",
    "plan_schedules",
    "plan_cache_dir",
    "clear_plan_cache",
    "HW_PROFILES",
]


# --------------------------------------------------------------------------
# plan config
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionPlanConfig:
    """Declarative selection + configuration of a distributed-attention call.

    ``backend="auto"`` resolves to ``local-flash`` when the sequence axis is
    unsharded (n <= 1) and to ``mesh`` otherwise.  ``a=None`` on the mesh
    backend means: autotune via the simulator when ``autotune`` is set,
    otherwise the sqrt-n heuristic (``best_square_a``).
    """

    backend: str = "auto"
    axis_name: Optional[str] = None
    n: int = 1
    a: Optional[int] = None
    causal: bool = False
    window: Optional[int] = None
    layout: str = "striped"  # striped (§3.7) | contiguous (SSM/hybrid, Ulysses)
    scale: Optional[float] = None
    block_q: int = 128
    block_kv: int = 128
    bwd_wire: str = "qdod"
    allow_concurrent_rings: bool = False
    mask: Optional[MaskSpec] = None  # first-class mask; supersedes causal/window
    # ring-transport mode (schedule.COMM_OVERLAP_MODES): serial pins each
    # step's permutes ahead of its blocks, overlap (default) leaves them in
    # flight during the blocks, bidir splits each hop into a half-payload
    # ppermute pair over both ring directions.  Bitwise-equal; changes the
    # simulated step cost, so it is part of the plan-cache key.
    comm_overlap: str = "overlap"
    paged: bool = False  # decode reads/writes a page pool through a block table
    # decode kernel variant: "auto" -> "native" (the paged Pallas kernel
    # reading the block table in-kernel, kernels/paged_decode.py) for the
    # paged cache wherever Pallas runs (TPU / REPRO_KERNELS=pallas), the
    # gather/band reference elsewhere; "native"/"gather" force either.
    decode_kernel: str = "auto"
    # KV-pool storage precision (paged only): "fp" keeps the cache dtype;
    # "int8"/"fp8" store pages quantized with fp32 per-(token, kv-head)
    # scale tables dequantized in-kernel (core/kv_quant.py).
    kv_dtype: str = "fp"
    # --- Figure-6 autotuning (simulator-planned tile + schedules) ---
    autotune: bool = False
    with_backward: bool = True
    hw_profile: str = "default"
    plan_cache_dir: Optional[str] = None  # None -> $REPRO_PLAN_CACHE_DIR or ~/.cache

    def __post_init__(self):
        S.validate_comm_overlap(self.comm_overlap)
        if self.mask is not None and (self.causal or self.window is not None):
            raise ValueError("pass either mask= or the legacy causal/window flags, not both")
        if self.decode_kernel not in ("auto", "native", "gather"):
            raise ValueError(
                f"unknown decode_kernel {self.decode_kernel!r}; "
                "expected auto | native | gather"
            )
        if self.kv_dtype not in kv_quant.KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {self.kv_dtype!r}; expected "
                + " | ".join(kv_quant.KV_DTYPES)
            )
        if self.kv_dtype != "fp" and not self.paged:
            raise ValueError(
                "kv_dtype quantization stores pages + scale tables; it "
                "requires the paged cache (paged=True)"
            )

    def resolved_backend(self) -> str:
        return resolve_backend_name(self)

    def mask_spec(self) -> MaskSpec:
        if self.mask is not None:
            return self.mask
        return MaskSpec.from_flags(self.causal, self.window)


def _resolve_decode_kernel(kernel: Optional[str], paged: bool) -> str:
    """"auto" -> the native paged kernel for the paged cache (the gather
    intermediate is exactly what it exists to kill) wherever the backend
    policy actually runs Pallas (TPU, or REPRO_KERNELS=pallas correctness
    runs) — "auto" off-TPU keeps the fast XLA gather/band reference, same
    policy as every other kernel (kernels/ops.py).  Explicit "native" runs
    the kernel interpret-mode off-TPU (except REPRO_KERNELS=ref, where
    ``_native_enabled`` serves it with the gather oracle); "gather" forces
    the oracle.  The dense cache defaults to the band path either way."""
    if kernel in (None, "auto"):
        if paged and ops.pallas_enabled():
            return "native"
        return "gather" if paged else "band"
    if kernel not in ("native", "gather"):
        # every route validates here (the n==1 paths never build a plan
        # config), so a typo'd variant fails loudly instead of silently
        # measuring the default path
        raise ValueError(
            f"unknown decode_kernel {kernel!r}; expected auto | native | gather"
        )
    return "band" if (kernel == "gather" and not paged) else kernel


def plan_from_ctx(
    ctx,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    layout: str = "striped",
    scale: Optional[float] = None,
    backend: Optional[str] = None,
    mask: Optional[MaskSpec] = None,
) -> AttentionPlanConfig:
    """Derive the attention plan a ``ParallelCtx`` implies (the knobs the
    model layers used to wire into ``MeshAttentionConfig`` by hand).
    ``mask`` supersedes the legacy causal/window pair."""
    impl = backend or ctx.attn_impl
    return AttentionPlanConfig(
        backend=impl,
        axis_name=ctx.sp_axis,
        n=ctx.sp_size,
        a=1 if impl == "ring" else ctx.mesh_a,
        causal=causal if mask is None else False,
        window=window if mask is None else None,
        mask=mask,
        layout=layout,
        scale=scale,
        block_q=ctx.block_q,
        block_kv=ctx.block_kv,
        bwd_wire=ctx.bwd_wire,
        allow_concurrent_rings=ctx.allow_concurrent_rings,
        comm_overlap=getattr(ctx, "comm_overlap", "overlap"),
        autotune=getattr(ctx, "attn_autotune", False),
        plan_cache_dir=getattr(ctx, "plan_cache_dir", None),
    )


# --------------------------------------------------------------------------
# backend registry
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered distributed-attention implementation.

    ``apply`` runs INSIDE ``shard_map`` on device-local chunks (exactly like
    the raw ops in ``core/``); ``step`` is the incremental-decode entry for
    cache-based backends.  Either may be None when the mode is unsupported.
    """

    name: str
    apply: Optional[Callable] = None  # (q, k, v, cfg, seg=None) -> o, local chunks
    step: Optional[Callable] = None  # decode step, see decode_attention_step
    description: str = ""


_REGISTRY: Dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown attention backend {name!r}; registered backends: "
            f"{', '.join(available_backends())}"
        ) from None


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


def resolve_backend_name(cfg: AttentionPlanConfig) -> str:
    if cfg.backend == "auto":
        return "local-flash" if cfg.n <= 1 else "mesh"
    get_backend(cfg.backend)  # raise early on unknown names
    return cfg.backend


# --------------------------------------------------------------------------
# simulator-planned schedules + on-disk plan cache
# --------------------------------------------------------------------------

HW_PROFILES: Dict[str, HardwareModel] = {
    "default": HardwareModel(),
    "tpu_v5e": HardwareModel(),
    # the paper's calibrated GPU cluster (also used by benchmarks/common.py)
    "paper_a100": HardwareModel(
        peak_flops=312e12, hbm_bw=2039e9, link_bw=25e9, attn_efficiency=0.45
    ),
}

_MEM_CACHE: Dict[str, Tuple[int, S.Schedule, Optional[S.Schedule]]] = {}


def plan_cache_dir(cfg: Optional[AttentionPlanConfig] = None) -> str:
    if cfg is not None and cfg.plan_cache_dir:
        return cfg.plan_cache_dir
    env = os.environ.get("REPRO_PLAN_CACHE_DIR")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro", "attention-plans")


def clear_plan_cache(cfg: Optional[AttentionPlanConfig] = None) -> None:
    _MEM_CACHE.clear()
    d = plan_cache_dir(cfg)
    if os.path.isdir(d):
        for fn in os.listdir(d):
            if fn.endswith(".json"):
                os.unlink(os.path.join(d, fn))


def _plan_key(cfg: AttentionPlanConfig, comm: CommModel, hw: HardwareModel) -> Tuple[str, dict]:
    """Cache key over everything the simulated plan depends on: the call's
    shape/dtype geometry, device count, tile request, mask, layout, and
    hardware profile.  The mask signature keeps masked and unmasked plans for
    the same (shape, dtype, n, hw) from ever colliding — mask structure
    changes both block cost and the pruned schedule."""
    desc = {
        "v": 5,
        "n": comm.n,
        "a": cfg.a,
        "seq": comm.seq,
        "hidden": comm.hidden,
        "kv_hidden": comm.kvh,
        "bytes_per_elem": comm.bytes_per_elem,
        "batch": comm.batch,
        "mask": cfg.mask_spec().signature(),
        "layout": cfg.layout,
        # paged and dense decode stacks must never share a plan entry: the
        # paged gather changes the achievable tile/arithmetic intensity
        "paged": cfg.paged,
        # gather and native decode kernels have different HBM traffic models,
        # so their plans must not collide either
        "decode_kernel": _resolve_decode_kernel(cfg.decode_kernel, cfg.paged),
        # quantized pools change per-page HBM bytes (1-byte elements + scale
        # tiles vs fp K/V) — fp and int8/fp8 plans must never collide
        "kv_dtype": cfg.kv_dtype,
        "with_backward": cfg.with_backward,
        "allow_concurrent_rings": cfg.allow_concurrent_rings,
        # overlap modes price steps differently (serial: comm+compute;
        # overlap: max+residual; bidir: per-direction bandwidth), so the
        # tuned tile/schedule may differ per mode — never share entries
        "comm_overlap": cfg.comm_overlap,
        "hw_profile": cfg.hw_profile,
        "hw": dataclasses.asdict(hw),
    }
    blob = json.dumps(desc, sort_keys=True)
    return hashlib.sha1(blob.encode()).hexdigest(), desc


def plan_schedules(
    cfg: AttentionPlanConfig, comm: CommModel
) -> Tuple[int, S.Schedule, Optional[S.Schedule]]:
    """Figure-6 planning through the cache: returns (a, fwd, bwd).

    ``cfg.a`` fixed -> ``autotune.plan_for`` (a=1 degenerates to the ring
    backend's schedule shape); ``cfg.a`` None -> ``autotune.tune`` argmin over
    every factorization of n.  Results are memoized in-process and persisted
    as JSON under :func:`plan_cache_dir` so later launches skip the simulator.
    """
    hw = HW_PROFILES.get(cfg.hw_profile)
    if hw is None:
        raise ValueError(
            f"unknown hw_profile {cfg.hw_profile!r}; known: {sorted(HW_PROFILES)}"
        )
    key, desc = _plan_key(cfg, comm, hw)
    if key in _MEM_CACHE:
        return _MEM_CACHE[key]

    cache_dir = plan_cache_dir(cfg)
    path = os.path.join(cache_dir, f"{key}.json")
    if os.path.exists(path):
        try:
            with open(path) as f:
                payload = json.load(f)
            fwd = S.schedule_from_json(payload["fwd"])
            bwd = S.schedule_from_json(payload["bwd"]) if payload.get("bwd") else None
            out = (int(payload["a"]), fwd, bwd)
            _MEM_CACHE[key] = out
            return out
        except (KeyError, TypeError, ValueError, json.JSONDecodeError):
            pass  # corrupt entry: fall through and re-plan

    kw = dict(
        mask=cfg.mask_spec(),
        layout=cfg.layout,
        with_backward=cfg.with_backward,
        allow_concurrent_rings=cfg.allow_concurrent_rings,
        comm_overlap=cfg.comm_overlap,
    )
    if cfg.a is not None:
        plan = autotune.plan_for(comm, cfg.a, hw, **kw)
    else:
        plan = autotune.tune(comm, hw, **kw)

    payload = {
        "key": desc,
        "a": plan.a,
        "b": plan.b,
        "fwd": S.schedule_to_json(plan.fwd),
        "bwd": S.schedule_to_json(plan.bwd) if plan.bwd else None,
        "sim": {"total_s": plan.total, "comm_bytes": plan.comm_bytes},
    }
    os.makedirs(cache_dir, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=cache_dir, suffix=".tmp")
    with os.fdopen(fd, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)  # atomic: concurrent launchers race benignly

    out = (plan.a, plan.fwd, plan.bwd)
    _MEM_CACHE[key] = out
    return out


def _comm_model_for(cfg: AttentionPlanConfig, q, k) -> CommModel:
    """CommModel from the call's global-logical shapes (q: [B, S, H, D])."""
    return CommModel(
        seq=int(q.shape[1]),
        hidden=int(q.shape[2] * q.shape[3]),
        n=cfg.n,
        kv_hidden=int(k.shape[2] * k.shape[3]),
        bytes_per_elem=int(jnp.dtype(q.dtype).itemsize),
        batch=int(q.shape[0]),
    )


# --------------------------------------------------------------------------
# backend implementations (run inside shard_map)
# --------------------------------------------------------------------------


def _mesh_cfg(
    cfg: AttentionPlanConfig,
    *,
    a: int,
    fwd: Optional[S.Schedule] = None,
    bwd: Optional[S.Schedule] = None,
) -> MeshAttentionConfig:
    return MeshAttentionConfig(
        axis_name=cfg.axis_name,
        n=cfg.n,
        a=a,
        causal=cfg.causal if cfg.mask is None else False,
        window=cfg.window if cfg.mask is None else None,
        mask=cfg.mask,
        layout=cfg.layout,
        scale=cfg.scale,
        fwd_schedule=fwd,
        bwd_schedule=bwd,
        bwd_wire=cfg.bwd_wire,
        block_q=cfg.block_q,
        block_kv=cfg.block_kv,
        allow_concurrent_rings=cfg.allow_concurrent_rings,
        comm_overlap=cfg.comm_overlap,
    )


def _mesh_apply(q, k, v, cfg: AttentionPlanConfig, seg=None):
    if cfg.autotune and cfg.n > 1:
        # inside shard_map q is the LOCAL chunk, so the CommModel geometry
        # would be wrong by a factor of n; distributed_attention resolves
        # autotuned plans from the global view before entering shard_map
        raise ValueError(
            "autotuned mesh plans must be resolved outside shard_map "
            "(use distributed_attention, or bake schedules via plan_schedules)"
        )
    a = cfg.a if cfg.a is not None else best_square_a(cfg.n)
    return mesh_attention(q, k, v, _mesh_cfg(cfg, a=a), seg=seg)


def _ring_apply(q, k, v, cfg: AttentionPlanConfig, seg=None):
    """Ring-Attention as the (a=1, b=n) special case — one-block-per-step
    ring schedule, identical kernels and ring machinery (paper §2.2)."""
    fwd = S.ring_forward_schedule(cfg.n) if cfg.n > 1 else None
    return mesh_attention(q, k, v, _mesh_cfg(cfg, a=1, fwd=fwd), seg=seg)


def _ulysses_apply(q, k, v, cfg: AttentionPlanConfig, seg=None):
    if cfg.layout != "contiguous":
        raise ValueError("Ulysses requires the contiguous layout")
    spec = cfg.mask_spec()
    if spec.kind == "block_sparse":
        raise ValueError("Ulysses does not support block-sparse masks")
    if spec.needs_segments and seg is None:
        raise ValueError(f"mask kind {spec.kind!r} needs a segment-id operand")
    return ulysses_attention(
        q, k, v, cfg.axis_name, cfg.n,
        causal=spec.is_causal, window=spec.window, scale=cfg.scale, seg=seg,
    )


def _local_flash_apply(q, k, v, cfg: AttentionPlanConfig, seg=None):
    spec = cfg.mask_spec()
    if spec.kind == "block_sparse":
        raise ValueError("block-sparse masks route through the mesh backend")
    if spec.needs_segments and seg is None:
        raise ValueError(f"mask kind {spec.kind!r} needs a segment-id operand")
    return ops.flash_attention(
        q, k, v, causal=spec.is_causal, window=spec.window, scale=cfg.scale,
        seg_q=seg, seg_kv=seg,
    )


def _decode_step_local(
    q, k_new, v_new, k_cache, v_cache, pos, layer, cfg: AttentionPlanConfig,
    bt=None, ks=None, vs=None,
):
    """One decode tick over the local cache slices of layer ``layer``
    (inside shard_map; caches stacked over layers).  With ``cfg.paged`` the
    caches are the physical page pools and ``bt`` is the block table (owner
    shard -> (page, offset) instead of -> slot row); ``ks``/``vs`` are the
    quantized pool's local scale tables — present, the new token quantizes
    on write and the step returns them updated (a 5-tuple instead of 3)."""
    if cfg.paged:
        upd = paged_cache_update(
            k_cache, v_cache, k_new, v_new, bt, pos, layer, cfg.axis_name, cfg.n,
            layout=cfg.layout, k_scale=ks, v_scale=vs,
        )
        k_cache, v_cache = upd[:2]
        ks, vs = upd[2:] if ks is not None else (None, None)
        o = paged_cache_decode(
            q, k_cache, v_cache, bt, pos, layer, cfg.axis_name, cfg.n,
            layout=cfg.layout, window=cfg.window, scale=cfg.scale,
            kernel=_resolve_decode_kernel(cfg.decode_kernel, paged=True),
            k_scale=ks, v_scale=vs,
        )
        return (o, k_cache, v_cache) + ((ks, vs) if ks is not None else ())
    k_cache, v_cache = sharded_cache_update(
        k_cache, v_cache, k_new, v_new, pos, layer, cfg.axis_name, cfg.n,
        layout=cfg.layout,
    )
    o = sharded_cache_decode(
        q, k_cache, v_cache, pos, layer, cfg.axis_name, cfg.n,
        layout=cfg.layout, window=cfg.window, scale=cfg.scale,
        kernel=_resolve_decode_kernel(cfg.decode_kernel, paged=False),
    )
    return o, k_cache, v_cache


def _decode_apply(q, k, v, cfg: AttentionPlanConfig, seg=None):
    raise ValueError(
        "the 'decode' backend is step-wise (sequence-sharded KV cache); "
        "call repro.core.dispatch.decode_attention_step instead of "
        "distributed_attention"
    )


register_backend(Backend(
    "mesh", apply=_mesh_apply,
    description="Mesh-Attention (a x b tile; autotunable via the simulator)",
))
register_backend(Backend(
    "ring", apply=_ring_apply,
    description="Ring-Attention baseline = mesh with a=1 and the ring schedule",
))
register_backend(Backend(
    "ulysses", apply=_ulysses_apply,
    description="DeepSpeed-Ulysses head-parallel (capped at the KV-head count)",
))
register_backend(Backend(
    "local-flash", apply=_local_flash_apply,
    description="single-device Pallas/reference flash attention (n == 1 fallback)",
))
register_backend(Backend(
    "decode", apply=_decode_apply, step=_decode_step_local,
    description="striped/contiguous sequence-sharded KV-cache flash-decode",
))


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------


def attention_in_shard_map(q, k, v, cfg: AttentionPlanConfig, seg=None):
    """Registry-dispatched local op for callers already inside shard_map.
    ``seg`` is the LOCAL [S/n] int32 segment-id chunk (document masks)."""
    return get_backend(resolve_backend_name(cfg)).apply(q, k, v, cfg, seg=seg)


def _require_ctx(ctx, cfg: AttentionPlanConfig):
    if ctx is None or ctx.mesh is None:
        raise ValueError(
            f"backend {cfg.backend!r} with n={cfg.n} needs a ParallelCtx "
            "carrying a mesh; pass ctx= or use n=1 / backend='local-flash'"
        )


def distributed_attention(q, k, v, *, cfg: AttentionPlanConfig, ctx=None, segments=None):
    """THE attention seam: every workload (train, prefill, benchmarks, tests)
    calls this with a declarative plan.

    q: [B, S, H, D]; k, v: [B, S, Hkv, D] — global-logical views under pjit.
    Causal striped-layout inputs must already be in stripe order (§3.7, the
    data pipeline / serve engine handle the permutation).  ``ctx`` supplies
    the mesh + batch sharding for the ``shard_map`` wrapper; it is optional
    when the plan resolves to the local backend.

    ``segments``: int32 [S] segment-id array for document/segment masks, in
    the SAME order as q/k/v (the caller stripes it with the tokens).  For a
    static ``MaskSpec.document`` mask it is synthesized (and striped) here
    when omitted.
    """
    mask_spec = cfg.mask_spec()
    if segments is None and mask_spec.kind == "document":
        seg_np = mask_spec.segment_array(int(q.shape[1]))
        if cfg.layout == "striped" and cfg.n > 1:
            seg_np = seg_np[stripe_permutation(int(q.shape[1]), cfg.n)]
        segments = jnp.asarray(seg_np)
    if segments is not None:
        segments = jnp.asarray(segments, jnp.int32)

    name = resolve_backend_name(cfg)
    if name == "local-flash" or cfg.n <= 1:
        return _local_flash_apply(q, k, v, cfg, seg=segments)

    backend = get_backend(name)
    if backend.apply is None:
        raise ValueError(f"backend {name!r} does not support the batched-attention mode")
    _require_ctx(ctx, cfg)

    if name == "mesh" and cfg.autotune:
        # plan at trace time (pure python) so the schedule is baked into the
        # hashable MeshAttentionConfig before shard_map tracing begins
        a, fwd, bwd = plan_schedules(cfg, _comm_model_for(cfg, q, k))
        macfg = _mesh_cfg(cfg, a=a, fwd=fwd, bwd=bwd)
        local = lambda q, k, v, seg=None: mesh_attention(q, k, v, macfg, seg=seg)
    else:
        local = lambda q, k, v, seg=None: backend.apply(q, k, v, cfg, seg=seg)

    spec = P(ctx.eff_batch_spec(q.shape[0]), cfg.axis_name, None, None)
    if segments is None:
        f = shard_map(
            local,
            mesh=ctx.shard_map_mesh(), in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return f(q, k, v)
    f = shard_map(
        lambda q, k, v, seg: local(q, k, v, seg=seg),
        mesh=ctx.shard_map_mesh(),
        in_specs=(spec, spec, spec, P(cfg.axis_name)),
        out_specs=spec,
        check_vma=False,
    )
    return f(q, k, v, segments)


def decode_attention_step(
    q,  # [B, 1, H, D]
    k_new,  # [B, 1, Hkv, D]
    v_new,
    k_cache,  # [L, B, cap(/n), Hkv, D]; sharded over the sequence axis — or,
    v_cache,  # paged: the pools [L, num_pages, n*page_size, Hkv, D]
    pos,  # int32 scalar, or [B] vector of per-slot positions
    ctx,
    *,
    layer,  # int32: the layer of the stacked caches this step serves
    window: Optional[int] = None,
    layout: str = "striped",
    scale: Optional[float] = None,
    block_table=None,  # int32 [B, max_pages]: switches to the paged cache
    decode_kernel: Optional[str] = None,  # None -> ctx.decode_kernel
    k_scale=None,  # [L, num_pages, n*page_size, Hkv] f32: quantized pool
    v_scale=None,
):
    """One token of cache-based decode through the 'decode' backend, for
    layer ``layer`` of caches stacked over the model's layers: the append
    scatters at ``[layer, ...]`` and the read indexes ``[layer, ...]``, so a
    caller that carries the stack through its layer loop updates it in place.

    Returns (o, new_k_cache, new_v_cache).  n == 1 runs the local update +
    flash-decode; otherwise the sequence-sharded cache path.  Vector ``pos``
    serves mixed-depth slots in one step (continuous batching).

    ``k_scale``/``v_scale`` (paged only) mark a QUANTIZED pool: pages hold
    int8/fp8 elements, the fp32 scale tables share the pool's sharding and
    page indexing, writes quantize, reads dequantize (in-kernel on the
    native path), and the step returns ``(o, k_cache, v_cache, k_scale,
    v_scale)``.

    ``block_table`` selects the PAGED cache: k/v are the physical page pools
    (position axis sharded over the sequence axis exactly like the dense cap
    axis) and each row's pages are resolved through the table.  The pool has
    no batch axis, so the paged step runs batch-REPLICATED over any data
    axes — every device applies the identical pool update (slots are few;
    pages, not rows, carry the memory).

    ``decode_kernel`` (default from ``ctx``) picks the band/gather oracle or
    the native paged kernel; "auto" resolves paged -> native, dense -> band.
    """
    n = ctx.sp_size
    pos = jnp.asarray(pos, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    if decode_kernel is None:
        decode_kernel = getattr(ctx, "decode_kernel", "auto")
    if k_scale is not None and block_table is None:
        raise ValueError("k_scale/v_scale (quantized pool) require block_table")
    paged = block_table is not None
    _resolve_decode_kernel(decode_kernel, paged=paged)  # a typo fails here, on every route
    quantized = k_scale is not None
    bt = jnp.asarray(block_table, jnp.int32) if paged else None
    cfg = AttentionPlanConfig(
        backend="decode", axis_name=ctx.sp_axis if n > 1 else None, n=max(n, 1),
        window=window, layout=layout, scale=scale, paged=paged,
        decode_kernel=decode_kernel, kv_dtype=kv_quant.kv_dtype_of(k_cache),
    )
    if n <= 1:
        return _decode_step_local(
            q, k_new, v_new, k_cache, v_cache, pos, layer, cfg,
            bt=bt, ks=k_scale, vs=v_scale,
        )
    step = get_backend("decode").step
    pos_spec = P(None) if pos.ndim else P()
    if not paged:
        bs = ctx.eff_batch_spec(q.shape[0])
        rep = P(bs, None, None, None)
        cache_spec = P(None, bs, ctx.sp_axis, None, None)
        f = shard_map(
            lambda q, kn, vn, kc, vc, pos, l: step(q, kn, vn, kc, vc, pos, l, cfg),
            mesh=ctx.shard_map_mesh(),
            in_specs=(rep, rep, rep, cache_spec, cache_spec,
                      P(bs) if pos.ndim else P(), P()),
            out_specs=(rep, cache_spec, cache_spec),
            check_vma=False,
        )
        return f(q, k_new, v_new, k_cache, v_cache, pos, layer)
    # the pool's page axis is unsharded, its position axis is sharded over
    # the sequence axis; everything else is replicated.  Quantized pools
    # thread their scale tables with the pool's sharding (the scale's
    # position axis is the pool's position axis) and get them back updated
    rep = P(None, None, None, None)
    pool_spec = P(None, None, ctx.sp_axis, None, None)
    scale_spec = P(None, None, ctx.sp_axis, None)
    scales = (k_scale, v_scale) if quantized else ()
    f = shard_map(
        lambda q, kn, vn, kp, vp, pos, l, bt, *sc: step(
            q, kn, vn, kp, vp, pos, l, cfg, bt=bt, ks=sc[0] if sc else None,
            vs=sc[1] if sc else None,
        ),
        mesh=ctx.shard_map_mesh(),
        in_specs=(rep, rep, rep, pool_spec, pool_spec, pos_spec, P(), P(None, None))
        + (scale_spec,) * len(scales),
        out_specs=(rep, pool_spec, pool_spec) + (scale_spec,) * len(scales),
        check_vma=False,
    )
    return f(q, k_new, v_new, k_cache, v_cache, pos, layer, bt, *scales)


def _chunk_step_local(
    q, k_new, v_new, k_cache, v_cache, starts, lens, wstarts, layer,
    cfg: AttentionPlanConfig, bt=None, ks=None, vs=None,
):
    """One prefill chunk over layer ``layer``'s local cache slices (inside
    shard_map; caches stacked over layers): scatter the chunk's KV by
    absolute position, then prefix-causal chunk attention over everything
    resident.  ``ks``/``vs`` carry a quantized pool's scale tables (chunked
    prefill and speculative verify write quantized exactly like decode);
    present, the step returns a 5-tuple."""
    if cfg.paged:
        upd = paged_cache_chunk_update(
            k_cache, v_cache, k_new, v_new, bt, starts, lens, wstarts, layer,
            cfg.axis_name, cfg.n, layout=cfg.layout, k_scale=ks, v_scale=vs,
        )
        k_cache, v_cache = upd[:2]
        ks, vs = upd[2:] if ks is not None else (None, None)
        o = paged_cache_chunk_decode(
            q, k_cache, v_cache, bt, starts, layer, cfg.axis_name, cfg.n,
            layout=cfg.layout, window=cfg.window, scale=cfg.scale,
            k_scale=ks, v_scale=vs,
        )
        return (o, k_cache, v_cache) + ((ks, vs) if ks is not None else ())
    k_cache, v_cache = sharded_cache_chunk_update(
        k_cache, v_cache, k_new, v_new, starts, lens, wstarts, layer,
        cfg.axis_name, cfg.n, layout=cfg.layout,
    )
    o = sharded_cache_chunk_decode(
        q, k_cache, v_cache, starts, layer, cfg.axis_name, cfg.n,
        layout=cfg.layout, window=cfg.window, scale=cfg.scale,
    )
    return o, k_cache, v_cache


def chunk_attention_step(
    q,  # [B, C, H, D] chunk queries (pad rows beyond lens compute garbage)
    k_new,  # [B, C, Hkv, D]
    v_new,
    k_cache,  # [L, B, cap(/n), Hkv, D] — or, paged: the pools
    v_cache,
    starts,  # int32 [B]: global position of each row's chunk base
    lens,  # int32 [B]: valid tokens per row (0 = inactive row, nothing written)
    write_starts,  # int32 [B]: skip KV writes below this position (shared prefix)
    ctx,
    *,
    layer,  # int32: the layer of the stacked caches this chunk serves
    window: Optional[int] = None,
    layout: str = "striped",
    scale: Optional[float] = None,
    block_table=None,  # int32 [B, max_pages]: switches to the paged cache
    k_scale=None,  # f32 scale tables: quantized pool (paged only)
    v_scale=None,
):
    """One continuous-prefill chunk for layer ``layer``: C tokens of row b
    land at global positions ``starts[b] .. starts[b]+lens[b]-1`` and attend
    prefix-causally to every resident position (row i sees <= starts[b]+i,
    within the window).  Returns (o, new_k_cache, new_v_cache) exactly like
    ``decode_attention_step`` — it is the same banded partial + lse psum with
    a multi-row q, so chunked prefill reproduces one-shot prefill bit-for-bit
    on the reference backend.  Chunks always run the band/gather path; the
    native paged kernel stays single-token.  ``k_scale``/``v_scale``
    (paged) quantize the chunk on write and extend the return to a 5-tuple,
    exactly like ``decode_attention_step``."""
    n = ctx.sp_size
    starts = jnp.asarray(starts, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    write_starts = jnp.asarray(write_starts, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    if k_scale is not None and block_table is None:
        raise ValueError("k_scale/v_scale (quantized pool) require block_table")
    paged = block_table is not None
    quantized = k_scale is not None
    bt = jnp.asarray(block_table, jnp.int32) if paged else None
    cfg = AttentionPlanConfig(
        backend="decode", axis_name=ctx.sp_axis if n > 1 else None, n=max(n, 1),
        window=window, layout=layout, scale=scale, paged=paged,
        kv_dtype=kv_quant.kv_dtype_of(k_cache),
    )
    if n <= 1:
        return _chunk_step_local(
            q, k_new, v_new, k_cache, v_cache, starts, lens, write_starts, layer,
            cfg, bt=bt, ks=k_scale, vs=v_scale,
        )
    if not paged:
        bs = ctx.eff_batch_spec(q.shape[0])
        rep = P(bs, None, None, None)
        cache_spec = P(None, bs, ctx.sp_axis, None, None)
        vec = P(bs)
        f = shard_map(
            lambda q, kn, vn, kc, vc, st, ln, ws, l: _chunk_step_local(
                q, kn, vn, kc, vc, st, ln, ws, l, cfg
            ),
            mesh=ctx.shard_map_mesh(),
            in_specs=(rep, rep, rep, cache_spec, cache_spec, vec, vec, vec, P()),
            out_specs=(rep, cache_spec, cache_spec),
            check_vma=False,
        )
        return f(q, k_new, v_new, k_cache, v_cache, starts, lens, write_starts, layer)
    rep = P(None, None, None, None)
    pool_spec = P(None, None, ctx.sp_axis, None, None)
    scale_spec = P(None, None, ctx.sp_axis, None)
    scales = (k_scale, v_scale) if quantized else ()
    f = shard_map(
        lambda q, kn, vn, kp, vp, st, ln, ws, l, bt, *sc: _chunk_step_local(
            q, kn, vn, kp, vp, st, ln, ws, l, cfg, bt=bt,
            ks=sc[0] if sc else None, vs=sc[1] if sc else None,
        ),
        mesh=ctx.shard_map_mesh(),
        in_specs=(rep, rep, rep, pool_spec, pool_spec, P(None), P(None), P(None),
                  P(), P(None, None)) + (scale_spec,) * len(scales),
        out_specs=(rep, pool_spec, pool_spec) + (scale_spec,) * len(scales),
        check_vma=False,
    )
    return f(q, k_new, v_new, k_cache, v_cache, starts, lens, write_starts, layer, bt,
             *scales)


def latent_wire_attention(
    q, wire, wire_params, kv_transform, *, cfg: AttentionPlanConfig, ctx, segments=None
):
    """Mesh-Attention with a compressed KV wire (beyond-paper §Perf): the
    opaque ``wire`` chunk circulates on the KV ring and ``kv_transform(chunk,
    wire_params) -> (k, v)`` expands it per-head at first use (e.g. MLA's
    latent).  Forward-only; ``wire_params`` stays replicated."""
    _require_ctx(ctx, cfg)
    a = cfg.a if cfg.a is not None else best_square_a(cfg.n)
    macfg = _mesh_cfg(cfg, a=a)

    spec = P(ctx.eff_batch_spec(q.shape[0]), cfg.axis_name, None, None)
    if segments is None:
        def inner(q, wire, wp):
            return mesh_attention_wire(q, wire, macfg, lambda chunk: kv_transform(chunk, wp))

        f = shard_map(
            inner,
            mesh=ctx.shard_map_mesh(), in_specs=(spec, spec, P()), out_specs=spec,
            check_vma=False,
        )
        return f(q, wire, wire_params)

    def inner_seg(q, wire, wp, seg):
        return mesh_attention_wire(
            q, wire, macfg, lambda chunk: kv_transform(chunk, wp), seg=seg
        )

    f = shard_map(
        inner_seg,
        mesh=ctx.shard_map_mesh(),
        in_specs=(spec, spec, P(), P(cfg.axis_name)),
        out_specs=spec,
        check_vma=False,
    )
    return f(q, wire, wire_params, jnp.asarray(segments, jnp.int32))
