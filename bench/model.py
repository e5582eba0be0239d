"""The program's model configuration for a benchmark configuration file."""

from __future__ import annotations

import dataclasses

import jax


def model_config(cfg_json: dict):
    """The registry's architecture with every size the file states: the
    file is the configuration as run."""
    from repro.configs import get_config

    base = get_config(cfg_json["registry"])
    if base.family != "dense" or base.mla is not None or base.qkv_bias or base.tie_embeddings:
        raise SystemExit(f"{cfg_json['registry']}: the reference covers dense llama-style "
                         "decoders only")
    return dataclasses.replace(
        base, num_layers=cfg_json["num_hidden_layers"], d_model=cfg_json["hidden_size"],
        num_heads=cfg_json["num_attention_heads"],
        num_kv_heads=cfg_json["num_key_value_heads"], head_dim=cfg_json["head_dim"],
        d_ff=cfg_json["intermediate_size"], vocab_size=cfg_json["vocab_size"],
        rope_theta=cfg_json["rope_theta"])


def abstract_params(cfg, dtype):
    from repro.models import transformer as tfm

    return jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype))
