"""Per-client personalization: local fine-tuning of the trained global model.

The classic FedAvg evaluation companion (e.g. "Improving Federated Learning
Personalization via Model Agnostic Meta Learning"-era protocol): after the
federated rounds finish, each client takes the global model and runs E
local full-batch steps on its OWN shard with a fresh optimizer, WITHOUT any
further averaging — measuring how much local adaptation buys on top of the
shared model. On non-IID shards this is the number that shows why
federation + personalization beats either alone; the reference has no
analogue (training always ends at the last averaged model).

One jit, vmapped over the client axis — embarrassingly parallel, no
collectives; works on both engines' states (any params pytree with a
leading client axis, including the 2-D engine's model-sharded layout,
where GSPMD keeps the sharding through the elementwise training math).
"""

from __future__ import annotations

from typing import Callable

import jax
import optax

from fedtpu.ops.metrics import metrics_from_confusion
from fedtpu.parallel.round import masked_client_mean
from fedtpu.training.client import make_local_eval_step, make_local_train_step
from fedtpu.training.task import classification_task


def build_personalize_fn(apply_fn: Callable,
                         tx: optax.GradientTransformation,
                         num_classes: int, steps: int) -> Callable:
    """Returns ``personalize(params, batch) -> (personal_params, metrics)``:
    ``steps`` local full-batch updates per client from the given (global)
    per-client params, fresh optimizer state, then per-client train-shard
    metrics of the personalized models. ``metrics`` carries ``per_client``
    and the empty-shard-masked ``client_mean`` (the same conventions as the
    round program, fedtpu.parallel.round.assemble_metrics)."""
    if steps < 1:
        raise ValueError(f"personalize steps must be >= 1, got {steps}")
    local_train = make_local_train_step(apply_fn, tx, local_steps=steps)
    local_eval = make_local_eval_step(
        classification_task(apply_fn, num_classes))

    @jax.jit
    def personalize(params, batch):
        x, y, mask = batch["x"], batch["y"], batch["mask"]
        opt_state = jax.vmap(tx.init)(params)
        personal, _, loss = jax.vmap(local_train)(params, opt_state,
                                                  x, y, mask)
        conf = jax.vmap(local_eval)(personal, x, y, mask)
        per_client = jax.vmap(metrics_from_confusion)(conf)
        return personal, {"per_client": per_client,
                          "client_mean": masked_client_mean(per_client,
                                                            mask),
                          "loss": loss}

    return personalize
