"""Training loop: jitted step, checkpoint/restart, preemption, stragglers,
elastic re-meshing, optional compressed cross-pod gradient reduction.

Fault-tolerance model (designed for 1000+ nodes, exercised on fake devices):
  * every state mutation goes through the atomic checkpointer; restart
    resumes from the newest *valid* checkpoint (corrupt ones are skipped);
  * SIGTERM/SIGINT set a flag; the loop checkpoints at the next step
    boundary and exits cleanly (preemption handling);
  * the data pipeline is a pure function of (seed, step), so a restarted or
    re-meshed run consumes the identical stream;
  * ``elastic_fit`` rebuilds the mesh from the *live* device set and
    reshards the restored state — a 512-chip run restarts on 256 chips;
  * the StepMonitor's "remesh" escalation flows through the same path.

Cross-pod gradient compression: when enabled and the mesh has a "pod" axis,
the step runs under ``shard_map(axis_names={"pod"})`` — manual over pods,
GSPMD-automatic inside — so per-pod gradients are quantized (int8 + error
feedback) before the slow DCN all-reduce.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.data.pipeline import make_batch
from repro.models import transformer as tfm
from repro.optim.adamw import AdamWConfig, OptState, adamw_update, init_opt_state
from repro.parallel import sharding as shd
from repro.parallel.compression import CompressionConfig, compressed_psum, init_error_state
from repro.parallel.context import ParallelCtx
from repro.train import checkpoint as ckpt
from repro.train.monitor import StepMonitor, StragglerPolicy

__all__ = ["TrainConfig", "make_train_step", "fit", "elastic_fit"]


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    seq: int = 128
    batch: int = 8
    seed: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    param_dtype: object = jnp.float32
    compression: Optional[CompressionConfig] = None
    docs: Optional[int] = None  # pack N documents per row (segment-mask attention)


def make_train_step(cfg: ModelConfig, ctx: ParallelCtx, opt_cfg: AdamWConfig,
                    compression: Optional[CompressionConfig] = None):
    """Returns jitted (params, opt_state, err, batch) -> (params, opt_state,
    err, metrics)."""

    use_comp = (
        compression is not None
        and compression.kind != "none"
        and ctx.mesh is not None
        and "pod" in ctx.mesh.shape
        and ctx.mesh.shape["pod"] > 1
    )

    def grads_and_metrics(params, batch, the_ctx):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, cfg, the_ctx, batch), has_aux=True
        )(params)
        return grads, metrics

    if not use_comp:

        def step_fn(params, opt_state, err, batch):
            grads, metrics = grads_and_metrics(params, batch, ctx)
            params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
            metrics.update(om)
            return params, opt_state, err, metrics

    else:
        # inside the manual-pod region, the model must not mention "pod"
        pod_ctx = dataclasses.replace(ctx, batch_axes=tuple(a for a in ctx.batch_axes if a != "pod"))

        def inner(params, opt_state, err, batch):
            # per-pod gradients (batch dim is pod-sharded outside; here each
            # pod sees its slice), then the compressed DCN all-reduce
            grads, metrics = grads_and_metrics(params, batch, pod_ctx)
            grads, err = compressed_psum(grads, "pod", err, compression)
            params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
            metrics = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"), metrics)
            metrics.update(om)
            return params, opt_state, err, metrics

        def step_fn(params, opt_state, err, batch):
            # partial-manual shard_map: only the pod axis is manual, so specs
            # may only mention "pod"; data/model sharding of params flows
            # through GSPMD from the arrays' own shardings
            rep = jax.tree.map(lambda _: P(), params)
            orep = OptState(P(), rep, rep)
            bspec = {k: (P() if k in ("positions", "segments") else P("pod")) for k in batch}
            f = shard_map(
                partial(inner),
                mesh=ctx.mesh,
                in_specs=(rep, orep, rep, bspec),
                out_specs=(rep, orep, rep, P()),
                axis_names={"pod"},
                check_vma=False,
            )
            return f(params, opt_state, err, batch)

    return jax.jit(step_fn, donate_argnums=(0, 1, 2))


def _shard_batch(batch, cfg, ctx: ParallelCtx, kind="train"):
    if ctx.mesh is None:
        return batch
    specs = shd.batch_specs(cfg, ctx, kind=kind, batch=batch["tokens"].shape[0])
    return {
        k: jax.device_put(v, NamedSharding(ctx.mesh, specs[k])) for k, v in batch.items()
    }


class _Preempt:
    def __init__(self):
        self.flag = False

    def install(self):
        def handler(signum, frame):
            self.flag = True

        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # not the main thread (tests)
        return self


def fit(
    cfg: ModelConfig,
    ctx: ParallelCtx,
    tcfg: TrainConfig,
    opt_cfg: Optional[AdamWConfig] = None,
    *,
    hooks: Optional[Dict[str, Callable]] = None,
) -> Dict:
    """Train; resume from tcfg.ckpt_dir when a valid checkpoint exists."""
    opt_cfg = opt_cfg or AdamWConfig(total_steps=tcfg.steps)
    hooks = hooks or {}
    preempt = _Preempt().install()
    monitor = StepMonitor(StragglerPolicy(action="checkpoint"))

    init = lambda: tfm.init_params(cfg, jax.random.PRNGKey(tcfg.seed), dtype=tcfg.param_dtype, ctx=ctx)
    err = {}
    if ctx.mesh is not None:
        abstract = jax.eval_shape(init)
        shardings = shd.param_shardings(abstract, ctx, "train")
        params = jax.jit(init, out_shardings=shardings)()
        # optimizer and error state live on the mesh like the params, as the
        # step returns them: state placed elsewhere would make step 1 compile
        # the step again
        replicated = NamedSharding(ctx.mesh, P())
        opt_state = jax.jit(init_opt_state, out_shardings=OptState(
            replicated, shardings, shardings))(params)
        if tcfg.compression:
            err = jax.jit(init_error_state, out_shardings=shardings)(params)
    else:
        params = init()
        opt_state = init_opt_state(params)
        if tcfg.compression:
            err = init_error_state(params)
    start_step = 0

    if tcfg.ckpt_dir is not None:
        try:
            state_like = {"params": params, "m": opt_state.m, "v": opt_state.v,
                          "step": jnp.zeros((), jnp.int32)}
            restored, ck_step = ckpt.restore(tcfg.ckpt_dir, state_like)
            params = restored["params"]
            opt_state = OptState(step=restored["step"], m=restored["m"], v=restored["v"])
            start_step = ck_step
        except (FileNotFoundError, IOError):
            pass

    step_fn = make_train_step(cfg, ctx, opt_cfg, tcfg.compression)
    saver = ckpt.AsyncCheckpointer(tcfg.ckpt_dir, keep=tcfg.keep) if tcfg.ckpt_dir else None
    history = []
    metrics = {}

    def save_now(step):
        if saver is None:
            return
        saver.save(step, {"params": params, "m": opt_state.m, "v": opt_state.v,
                          "step": opt_state.step})
        saver.wait()

    step = start_step
    for step in range(start_step, tcfg.steps):
        if preempt.flag:
            save_now(step)
            return {"interrupted": True, "step": step, "history": history}
        batch = make_batch(
            cfg, tcfg.seq, tcfg.batch, seed=tcfg.seed, step=step, ctx=ctx, docs=tcfg.docs
        )
        batch = _shard_batch(batch, cfg, ctx)
        t0 = time.perf_counter()
        params, opt_state, err, metrics = step_fn(params, opt_state, err, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        action = monitor.record(dt)
        history.append(float(metrics["loss"]))
        if "on_step" in hooks:
            hooks["on_step"](step, metrics)
        if action == "checkpoint" or (
            tcfg.ckpt_dir and (step + 1) % tcfg.ckpt_every == 0
        ):
            save_now(step + 1)
        if "fail_at" in hooks and hooks["fail_at"] == step:
            raise RuntimeError(f"injected failure at step {step}")
    save_now(tcfg.steps)
    return {
        "interrupted": False,
        "step": tcfg.steps,
        "history": history,
        "final_loss": history[-1] if history else None,
        "straggler_events": monitor.events,
        "params": params,
    }


def elastic_fit(make_ctx: Callable[[], ParallelCtx], cfg, tcfg, opt_cfg=None, max_restarts=2):
    """Restart-on-failure wrapper: rebuilds the mesh from the live device set
    (make_ctx) and resumes from the newest valid checkpoint.  A shrunk or
    grown device set reshards transparently at restore."""
    attempts = 0
    while True:
        try:
            return fit(cfg, make_ctx(), tcfg, opt_cfg)
        except RuntimeError:
            attempts += 1
            if attempts > max_restarts:
                raise
