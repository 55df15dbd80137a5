"""What a federated job learns, as one object the engines take.

An engine trains parameters on client shards and reports how well; what the
loss is, what "how well" means and how it adds up over rows and clients is
the task's. Everything a task reports comes from *sufficient statistics*
that sum over rows and over clients (a confusion matrix; counts of tokens),
so a client's metric, the pooled metric and a held-out evaluation are the
same function of differently summed statistics.

* ``classification`` is the reference's: softmax cross-entropy, a ``(K, K)``
  confusion matrix, and accuracy / weighted precision / recall / F1 from it
  (fedtpu.ops.metrics), number for number what the engines computed before
  this interface existed.
* ``next_token`` is the language model's: summed next-token loss, correct
  predictions and counted tokens, reported as token accuracy and perplexity.
  No ``(V, V)`` array exists anywhere for it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from fedtpu.models.registry import LANGUAGE_MODELS
from fedtpu.ops.losses import masked_cross_entropy
from fedtpu.ops.metrics import (METRIC_NAMES, confusion_matrix,
                                metrics_from_confusion)


@dataclasses.dataclass(frozen=True)
class Task:
    """``loss(params, x, y, mask) -> (loss, stats)``: the mean loss over the
    units the task counts (rows; tokens) and the statistics of the same
    forward pass. ``stats(params, x, y, mask)``: the statistics alone (a
    forward pass: in-round evaluation of the resident engines, held-out
    evaluation). ``weight(x, y, mask)``: how many units the loss counts in
    these rows, a function of the data alone (FedAvg's data-size weight).
    ``metrics(stats) -> {name: scalar}`` for ``metric_names``, the first of
    which is ``accuracy`` (what checkpoint retention ranks by).
    ``counters(stats)``, where a task has them: what the run's registry
    counts besides the metrics, from a round's pooled statistics; the names
    in ``gauges`` are set, the others added."""

    name: str
    metric_names: Tuple[str, ...]
    loss: Callable
    stats: Callable
    weight: Callable
    metrics: Callable
    counters: Optional[Callable] = None
    gauges: Tuple[str, ...] = ()


def classification_task(apply_fn: Callable, num_classes: int) -> Task:
    def stats(params, x, y, mask):
        preds = jnp.argmax(apply_fn(params, x), axis=-1)
        return confusion_matrix(y, preds, mask, num_classes)

    def loss(params, x, y, mask):
        logits = apply_fn(params, x)
        conf = confusion_matrix(y, jnp.argmax(logits, axis=-1), mask,
                                num_classes)
        return masked_cross_entropy(logits, y, mask), conf

    return Task(name="classification", metric_names=METRIC_NAMES, loss=loss,
                stats=stats, weight=lambda x, y, mask: mask.sum(),
                metrics=metrics_from_confusion)


# The weight of a prediction module's loss in the sum that is differentiated:
# no key of a published config (DeepSeek-V3's, arXiv:2412.19437 section 2.2).
MTP_LOSS_WEIGHT = 0.3


def next_token_task(stats_fn: Callable, model_cfg) -> Task:
    """``stats_fn(params, x, mask)`` is the model's own (the second of
    ``fedtpu.models.registry.build_model``'s pair for a language model): rows
    ``x (N, 2, T)`` of token and segment ids; labels are the rows' own next
    tokens, so ``y`` is unused.

    A model with a multi-token-prediction module (``num_nextn_predict_layers``
    1, xing4's preset) hands out a second loss's sums
    (``mtp_loss_sum``, ``mtp_count``): the loss that is differentiated is
    the main loss's mean plus ``MTP_LOSS_WEIGHT`` times the module's, each
    over its own valid positions, and ``main_loss`` / ``mtp_loss`` stand
    beside accuracy and perplexity (the main head's) among the metrics."""
    from fedtpu.ops.lm_head import next_token_targets

    second = model_cfg.num_nextn_predict_layers > 0
    mean = lambda total, n: total / jnp.maximum(n, 1.0)

    def stats(params, x, y, mask):
        return stats_fn(params, x, mask)

    def loss(params, x, y, mask):
        s = stats_fn(params, x, mask)
        total = s["loss_sum"] / jnp.maximum(s["count"], 1.0)
        if second:
            total = total + MTP_LOSS_WEIGHT * mean(
                s["mtp_loss_sum"], s["mtp_count"])
        return total, s

    def weight(x, y, mask):
        valid = jax.vmap(lambda row: next_token_targets(row[0], row[1])[1])(x)
        return (valid.sum(axis=1) * mask).sum()

    def metrics(s):
        n = jnp.maximum(s["count"], 1.0)
        found = {"accuracy": s["correct"] / n,
                 "perplexity": jnp.exp(s["loss_sum"] / n)}
        if second:
            found.update(main_loss=s["loss_sum"] / n,
                         mtp_loss=mean(s["mtp_loss_sum"], s["mtp_count"]))
        return found

    # a model whose expert layers compute every expert they route over
    # (olmoe) owes this many assignments a real token
    assignments = model_cfg.num_experts_per_tok * model_cfg.num_hidden_layers
    # what a model that holds a share of its experts counts besides, and
    # what its other layers do (nemotron_h's state-space layers; xing4's
    # residual modules and prediction module; kimi_linear's and
    # solar_open2's delta-rule recurrences; phi4_flash's selective scans and
    # its window): read off the statistics it hands out
    share = {"moe_assignments_held": "assignments_held",
             "moe_rows_computed": "rows_computed",
             "ssm_positions": "ssm_positions",
             "ssm_document_restarts": "ssm_restarts",
             "ssm_fused_pass_positions": "ssm_fused_passes",
             "hc_mix_positions": "hc_mix_positions",
             "hc_fused_positions": "hc_fused",
             "mtp_positions": "mtp_count",
             "kda_positions": "kda_positions",
             "kda_document_restarts": "kda_restarts",
             "kda_fused_scan_positions": "kda_fused_scan",
             "kda_head_steps": "kda_head_steps",
             "kda_steps_over_one": "kda_steps_over_one",
             "s6_positions": "s6_positions",
             "s6_chunked_scan_positions": "s6_chunked_scan",
             "s6_fused_scan_positions": "s6_fused_scan",
             "s6_fused_conv_positions": "s6_fused_conv",
             "s6_document_restarts": "s6_restarts",
             "lm_attention_pairs": "attention_pairs",
             "lm_window_pairs": "window_pairs"}

    def counters(s):
        own = {name: s[key] for name, key in share.items() if key in s}
        lm = {"lm_tokens": s["count"],
              "lm_padding_tokens": s["padding"],
              "lm_fused_attention_positions": s["fused_attention"],
              "lm_attention_blocks_computed": s["attention_blocks_computed"],
              "lm_attention_blocks_causal": s["attention_blocks_causal"]}
        if "expert_load" not in s:
            # a model without an expert layer (phi4_flash) routes nothing
            # and counts none of the experts' counters
            return {**lm, **own}
        load = s["expert_load"].astype(jnp.float32)
        routed = load.sum()
        if "assignments_held" in s:
            # its own experts' assignments, all of them inside the blocks
            # it computed; the rest belong to other chips
            dropped = s["assignments_held"] - s["rows_held_computed"]
            extra = {"moe_assignments_total": routed, **own}
        else:
            # every assignment of a real token is computed
            dropped, extra = assignments * s["tokens"] - routed, {}
        if "hc_mix_positions" in s:
            # gauges of the round: the width the tiled attention core ran a
            # head at (0: the XLA body), and the mean over the round's steps
            # of the largest |rowsum - 1|, |colsum - 1| of a step's H_res
            steps = jnp.maximum(s["sequences"], 1.0)
            extra.update(
                attention_padded_width=s["attention_padded_width"]
                / jnp.maximum(s["tokens"] + s["padding"], 1.0),
                hc_sinkhorn_residual=s["hc_sinkhorn_residual"] / steps)
        if "kda_log_decay_min" in s:
            # a gauge of the round: the mean over its steps of the most
            # negative cumulative log-decay inside one chunk of a step's
            # recurrences (under -88, ``exp(-G)`` would overflow float32)
            extra.update(kda_log_decay_min=s["kda_log_decay_min"]
                         / jnp.maximum(s["sequences"], 1.0))
        if "kda_step_max" in s:
            # and the mean over its steps of a step's largest ``beta`` at a
            # real position (under 1, or under 2 where the model allows
            # negative eigenvalues)
            extra.update(kda_step_max=s["kda_step_max"]
                         / jnp.maximum(s["sequences"], 1.0))
        if second:
            extra.update(main_loss=mean(s["loss_sum"], s["count"]),
                         mtp_loss=mean(s["mtp_loss_sum"], s["mtp_count"]))
        return {
            "moe_tokens_routed": routed,
            "moe_tokens_dropped": dropped,      # 0 by construction
            "moe_expert_load_max_over_mean": load.max() / jnp.maximum(
                load.mean(), 1.0),
            **lm,
            "moe_grouped_kernel_positions": s["grouped_experts"],
            "moe_expert_load": s["expert_load"],
            **extra,
        }

    names = ("accuracy", "perplexity") + (
        ("main_loss", "mtp_loss") if second else ())
    return Task(name="next_token", metric_names=names,
                loss=loss, stats=stats, weight=weight, metrics=metrics,
                counters=counters,
                gauges=("moe_expert_load_max_over_mean",
                        "attention_padded_width", "hc_sinkhorn_residual",
                        "main_loss", "mtp_loss", "kda_log_decay_min",
                        "kda_step_max"))


def build_task(model_cfg, model_fn: Callable, num_classes: int) -> Task:
    """The task of a model kind: ``model_fn`` is the second of
    ``build_model``'s pair."""
    if model_cfg.kind in LANGUAGE_MODELS:
        return next_token_task(model_fn, model_cfg)
    return classification_task(model_fn, num_classes)
