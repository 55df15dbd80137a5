"""Model registry: ModelConfig -> (init_fn, apply_fn)."""

from __future__ import annotations

import functools
import importlib

import jax.numpy as jnp

from fedtpu.config import ModelConfig
from fedtpu.models.mlp import mlp_init, mlp_apply
from fedtpu.models.convnet import convnet_init, convnet_apply

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float16": jnp.float16}
# The language models: kind -> the module that is the model. A module has
# one interface: ``check(cfg)`` (raises where the configuration cannot be
# built), ``init(key, cfg, param_dtype)``, ``sequence_stats(params, row, cfg,
# compute_dtype)`` and ``PER_ROW`` (the statistics a padded row must not
# count). Imported when asked for: a job of another kind never pays for it.
LANGUAGE_MODELS = {"olmoe": "fedtpu.models.olmoe",
                   "nemotron_h": "fedtpu.models.nemotron_h",
                   "xing4": "fedtpu.models.xing4",
                   "kimi_linear": "fedtpu.models.kimi_linear",
                   "solar_open2": "fedtpu.models.kimi_linear",
                   "phi4_flash": "fedtpu.models.phi4_flash"}


def build_model(cfg: ModelConfig):
    """Return ``(init_fn(key) -> params, apply_fn(params, x) -> logits)``.
    For the language models (``LANGUAGE_MODELS``) the second is
    ``stats_fn(params, x, mask) -> statistics``, the module's
    ``sequence_stats`` summed over rows (``fedtpu.models.layers.rows_stats``):
    a vocabulary-sized model hands out sums over tokens, never its logits."""
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
    if cfg.kind in LANGUAGE_MODELS:
        from fedtpu.models.layers import rows_stats
        model = importlib.import_module(LANGUAGE_MODELS[cfg.kind])
        model.check(cfg)
        init = functools.partial(model.init, cfg=cfg, param_dtype=param_dtype)
        stats = functools.partial(
            rows_stats(model.sequence_stats, model.PER_ROW), cfg=cfg,
            compute_dtype=compute_dtype or param_dtype)
        return init, stats
    raise ValueError(f"unknown model kind {cfg.kind!r}")
