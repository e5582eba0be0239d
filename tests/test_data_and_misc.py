"""Data-pipeline determinism/striping, pipeline-stage bookkeeping, and
misc substrate edge cases (property-style, fast)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.compat import make_mesh
from repro.configs import get_config
from repro.data.pipeline import batch_spec_shapes, make_batch
from repro.parallel.context import ParallelCtx


def test_batch_deterministic_in_seed_and_step():
    cfg = get_config("granite-8b").reduced()
    a = make_batch(cfg, 32, 2, seed=7, step=3)
    b = make_batch(cfg, 32, 2, seed=7, step=3)
    c = make_batch(cfg, 32, 2, seed=7, step=4)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_labels_are_next_tokens_under_striping():
    """labels[j] must be the token following tokens[j] in TRUE positions,
    whatever the layout permutation."""

    class FakeCtx(ParallelCtx):
        pass

    cfg = get_config("granite-8b").reduced()
    # striping only activates with sp>1; emulate by calling the permutation
    from repro.core.tiling import stripe_permutation

    n, S = 4, 32
    batch = make_batch(cfg, S, 2, seed=0)
    perm = stripe_permutation(S, n)
    striped_tokens = np.asarray(batch["tokens"])[:, perm]
    striped_labels = np.asarray(batch["labels"])[:, perm]
    # invariant: for every striped index j, label == original next token
    tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["labels"])
    for j in range(S):
        p = perm[j]
        assert (striped_tokens[:, j] == tokens[:, p]).all()
        assert (striped_labels[:, j] == labels[:, p]).all()


def test_batch_spec_shapes_cover_frontends():
    for arch, key in [("whisper-base", "frames"), ("pixtral-12b", "patches")]:
        cfg = get_config(arch)
        shapes = batch_spec_shapes(cfg, 64, 2)
        assert key in shapes
        assert shapes["tokens"][0] == (2, 64)


@given(st.integers(1, 6), st.integers(1, 6))
@settings(max_examples=30, deadline=None)
def test_eff_batch_axes_divisibility(pod, data):
    """The chosen batch-axis subset's size product always divides the batch."""
    import jax

    if pod * data > jax.device_count():
        # mesh construction needs real devices; emulate with math-only check
        return
    mesh = make_mesh((pod, data), ("pod", "data"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("pod", "data"), sp_axis=None)
    for b in (1, 2, 3, 4, 6, 8, 12, 16):
        axes = ctx.eff_batch_axes(b)
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        assert b % prod == 0


def test_pipeline_stages_reshape_and_errors():
    from repro.parallel.pipeline import pipeline_stages

    p = {"w": jnp.zeros((8, 3, 3))}
    staged = pipeline_stages(p, 4)
    assert staged["w"].shape == (4, 2, 3, 3)
    with pytest.raises(ValueError):
        pipeline_stages({"w": jnp.zeros((7, 3))}, 4)


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    from repro.train import checkpoint as ckpt

    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        ckpt.restore(d, {"w": jax.ShapeDtypeStruct((5,), jnp.float32)})


def test_reduced_configs_preserve_family_features():
    """reduced() must keep the family-defining switches intact."""
    for arch in ("mixtral-8x7b", "qwen2-moe-a2.7b"):
        r = get_config(arch).reduced()
        assert r.moe is not None and r.moe.top_k >= 1
    assert get_config("mamba2-370m").reduced().ssm is not None
    h = get_config("hymba-1.5b").reduced()
    assert h.hybrid and h.ssm is not None and h.window
    assert get_config("minicpm3-4b").reduced().mla is not None
    w = get_config("whisper-base").reduced()
    assert w.encoder_layers > 0 and not w.mlp_gated and w.norm == "layernorm"
    assert get_config("pixtral-12b").reduced().num_patches > 0


def test_sharding_spec_rules():
    """Spec rules on an AbstractMesh (no devices needed): serve = row/col
    parallel over model; train = largest-dim FSDP; expert weights follow the
    EP/TP divisibility rule; the stacked layer dim is never sharded."""
    from jax.sharding import PartitionSpec as P

    from repro.compat import abstract_mesh
    from repro.parallel import sharding as shd

    mesh = abstract_mesh((16, 16), ("data", "model"))
    ctx = ParallelCtx(mesh=mesh, batch_axes=("data",), sp_axis="model")
    params = {
        "embed": jnp.zeros((4096, 512)),
        "layers": {
            "attn": {"wq": jnp.zeros((4, 512, 1024)), "wo": jnp.zeros((4, 1024, 512))},
            "moe": {
                "we1": jnp.zeros((4, 64, 512, 352)),  # E=64 % 16 == 0 -> EP
                "we2": jnp.zeros((4, 64, 352, 512)),
            },
        },
    }
    serve = shd.param_specs(params, ctx, "serve")
    assert serve["layers"]["attn"]["wq"] == P(None, None, "model")  # column
    assert serve["layers"]["attn"]["wo"] == P(None, "model", None)  # row
    assert serve["embed"] == P("model", None)
    train = shd.param_specs(params, ctx, "train")
    assert train["layers"]["attn"]["wq"][0] is None  # L never sharded
    assert train["layers"]["moe"]["we1"][1] == "model"  # EP expert dim
    # TP fallback when experts don't divide the axis (E=8 on 16)
    tp = shd.param_specs({"we1": jnp.zeros((4, 8, 512, 352))}, ctx, "train")
    assert tp["we1"][1] is None and tp["we1"][3] == "model"


def test_stripe_window_mask_composition():
    """Striped + sliding-window band == token-level windowed causal mask."""
    from repro.core.tiling import stripe_permutation, striped_causal_offset
    from repro.kernels.ref import band_mask

    n, m, W = 4, 8, 5
    S = n * m
    perm = stripe_permutation(S, n)
    for qc in range(n):
        for kc in range(n):
            got = np.asarray(
                band_mask(m, m, (qc, kc, 0, W - 1), stride_q=n, stride_kv=n)
            )
            qt = perm[qc * m : (qc + 1) * m]
            kt = perm[kc * m : (kc + 1) * m]
            want = (qt[:, None] >= kt[None, :]) & (qt[:, None] - kt[None, :] < W)
            assert (got == want).all(), (qc, kc)


def test_compile_cache_dir_is_the_checkout_or_the_env(tmp_path, monkeypatch):
    """The default cache lives in the checkout; an installed copy outside a
    checkout leaves it off; ``JAX_COMPILATION_CACHE_DIR`` wins when set."""
    from pathlib import Path

    from repro.launch import compile_cache

    root = Path(__file__).resolve().parents[1]
    assert compile_cache.checkout_cache_dir() == str(root / ".jax_cache")

    installed = tmp_path / "lib" / "python3.12" / "site-packages" / "repro" / "launch"
    installed.mkdir(parents=True)
    monkeypatch.setattr(compile_cache, "__file__", str(installed / "compile_cache.py"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.checkout_cache_dir() is None
    assert compile_cache.enable_compile_cache() is None

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "cache")
