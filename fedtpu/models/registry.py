"""Model registry: ModelConfig -> (init_fn, apply_fn)."""

from __future__ import annotations

import functools

import jax.numpy as jnp

from fedtpu.config import ModelConfig
from fedtpu.models.mlp import mlp_init, mlp_apply
from fedtpu.models.convnet import convnet_init, convnet_apply

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float16": jnp.float16}


def build_model(cfg: ModelConfig):
    """Return ``(init_fn(key) -> params, apply_fn(params, x) -> logits)``.
    For the language models (``kind='olmoe'``, ``'nemotron_h'``, ``'xing4'``,
    ``'kimi_linear'``) the second is ``stats_fn(params, x, mask) ->
    statistics`` (fedtpu.models.olmoe.olmoe_stats,
    nemotron_h.nemotron_h_stats, xing4.xing4_stats,
    kimi_linear.kimi_linear_stats): a
    vocabulary-sized model hands out sums over tokens, never its logits."""
    param_dtype = _DTYPES[cfg.param_dtype]
    compute_dtype = (None if cfg.compute_dtype == cfg.param_dtype
                     else _DTYPES[cfg.compute_dtype])
    if cfg.kind == "mlp":
        init = functools.partial(mlp_init, input_dim=cfg.input_dim,
                                 hidden_sizes=cfg.hidden_sizes,
                                 num_classes=cfg.num_classes,
                                 param_dtype=param_dtype)
        apply = functools.partial(mlp_apply, compute_dtype=compute_dtype)
        return init, apply
    if cfg.kind == "convnet":
        init = functools.partial(convnet_init, image_shape=cfg.image_shape,
                                 conv_channels=cfg.conv_channels,
                                 hidden=cfg.hidden_sizes[0],
                                 num_classes=cfg.num_classes,
                                 param_dtype=param_dtype)
        apply = functools.partial(convnet_apply, compute_dtype=compute_dtype)
        return init, apply
    if cfg.kind == "olmoe":
        # imported here: a job of another kind never pays for it
        from fedtpu.models.olmoe import olmoe_init, olmoe_stats
        if cfg.hidden_size % cfg.num_attention_heads:
            raise ValueError(f"hidden_size {cfg.hidden_size} does not divide "
                             f"into {cfg.num_attention_heads} heads")
        init = functools.partial(olmoe_init, cfg=cfg, param_dtype=param_dtype)
        stats = functools.partial(
            olmoe_stats, cfg=cfg,
            compute_dtype=compute_dtype or param_dtype)
        return init, stats
    if cfg.kind == "nemotron_h":
        from fedtpu.models import nemotron_h as nh
        nh.layer_kinds(cfg)         # the pattern's letters and its length
        nh.experts_share(cfg)
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(
                f"{cfg.num_attention_heads} query heads do not divide over "
                f"{cfg.num_key_value_heads} key-value heads")
        if cfg.mamba_num_heads % cfg.n_groups:
            raise ValueError(
                f"{cfg.mamba_num_heads} state-space heads do not divide "
                f"into {cfg.n_groups} groups")
        init = functools.partial(nh.nemotron_h_init, cfg=cfg,
                                 param_dtype=param_dtype)
        stats = functools.partial(
            nh.nemotron_h_stats, cfg=cfg,
            compute_dtype=compute_dtype or param_dtype)
        return init, stats
    if cfg.kind == "xing4":
        from fedtpu.models import xing4
        xing4.layer_kinds(cfg)      # the depth, the leading dense layers
        xing4.experts_share(cfg)
        init = functools.partial(xing4.xing4_init, cfg=cfg,
                                 param_dtype=param_dtype)
        stats = functools.partial(
            xing4.xing4_stats, cfg=cfg,
            compute_dtype=compute_dtype or param_dtype)
        return init, stats
    if cfg.kind == "kimi_linear":
        from fedtpu.models import kimi_linear
        kimi_linear.layer_kinds(cfg)    # the two lists, no prediction module
        kimi_linear.experts_share(cfg)
        init = functools.partial(kimi_linear.kimi_linear_init, cfg=cfg,
                                 param_dtype=param_dtype)
        stats = functools.partial(
            kimi_linear.kimi_linear_stats, cfg=cfg,
            compute_dtype=compute_dtype or param_dtype)
        return init, stats
    raise ValueError(f"unknown model kind {cfg.kind!r}")
