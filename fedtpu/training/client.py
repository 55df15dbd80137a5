"""Per-client local training and evaluation as pure functions.

These are the fedtpu analogues of the reference client methods:

* ``make_local_train_step`` == ``train_one_epoch``
  (FL_CustomMLPCLassifierImplementation_Multiple_Rounds.py:63-73): ONE
  full-batch forward/backward/optimizer step on the client's whole shard per
  round — no minibatching, no DataLoader — followed by the LR-schedule step
  (folded into the optax schedule, see fedtpu.ops.optim).
* ``make_local_eval_step`` == ``evaluate_local`` (:75-91): argmax predictions
  on the client's own training shard (the reference never evaluates held-out
  data in the round loop), reduced to the task's statistics on device (for
  classification a confusion matrix) instead of shipping predictions to host
  sklearn.

Being pure functions of ``(params, opt_state, batch)``, they vmap over the
per-device client block inside the shard_map round and jit anywhere on their
own (single-client training is the num_clients=1 special case, no separate
code path).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import optax

from fedtpu.ops.losses import masked_cross_entropy


def make_local_train_step(apply_fn: Callable,
                          tx: optax.GradientTransformation,
                          local_steps: int = 1,
                          prox_mu: float = 0.0,
                          scaffold: bool = False,
                          task_loss: Callable | None = None) -> Callable:
    """Returns ``step(params, opt_state, x, y, mask) ->
    (params, opt_state, loss)`` — ``local_steps`` full-batch updates.

    Defaults reproduce the reference exactly: ONE step per round
    (``train_one_epoch``, FL_CustomMLP...:63-73). ``local_steps=E`` is
    classic FedAvg's E local epochs (full-batch, so epoch == step here);
    the LR schedule advances per optimizer update, as the reference's
    StepLR does (:73). ``prox_mu`` adds the FedProx proximal term
    ``mu/2 * ||w - w_global||^2`` against the round-start params — zero
    gradient at the anchor, so it only matters when ``local_steps > 1``
    (it bounds client drift on non-IID shards).

    ``scaffold=True`` changes the signature to ``step(params, opt_state,
    x, y, mask, correction)``: the SCAFFOLD drift correction
    ``c - c_i`` (a params-shaped pytree) is ADDED to the raw gradient
    before the optimizer sees it — Karimireddy et al. 2020's local rule
    ``y <- y - lr*(g(y) - c_i + c)``, generalized to any optax optimizer
    by correcting the gradient rather than hardcoding SGD. The variate
    bookkeeping lives in the round engine (fedtpu.parallel.round).

    ``task_loss``: a task's ``loss(params, x, y, mask) -> (loss, statistics)``
    (fedtpu.training.task) in place of the masked cross-entropy of
    ``apply_fn``'s logits; the statistics are not used here."""

    if local_steps < 1:
        raise ValueError(f"local_steps must be >= 1, got {local_steps}")
    if prox_mu < 0:
        raise ValueError(f"prox_mu must be >= 0, got {prox_mu} "
                         "(negative mu amplifies drift instead of bounding it)")

    def step(params, opt_state, x, y, mask, correction=None):
        anchor = params

        def one(carry, _):
            p, s = carry

            def loss_fn(q):
                # The optimized objective may include the prox penalty, but
                # the REPORTED loss stays plain masked CE — comparable
                # across prox/non-prox runs and to the reference's loss.
                ce = (masked_cross_entropy(apply_fn(q, x), y, mask)
                      if task_loss is None else task_loss(q, x, y, mask)[0])
                obj = ce
                if prox_mu:
                    sq = sum(jnp.sum(jnp.square(a - b))
                             for a, b in zip(jax.tree.leaves(q),
                                             jax.tree.leaves(anchor)))
                    obj = ce + 0.5 * prox_mu * sq
                return obj, ce

            (_, ce), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
            if scaffold:
                # Cast-preserving add: the optimizer's state dtypes follow
                # the grad dtypes, so the correction must not promote them
                # (bf16 params + f32-reduced variates would).
                grads = jax.tree.map(lambda g, c: (g + c).astype(g.dtype),
                                     grads, correction)
            updates, s = tx.update(grads, s, p)
            return (optax.apply_updates(p, updates), s), ce

        if local_steps == 1:
            (params, opt_state), loss = one((params, opt_state), None)
        else:
            (params, opt_state), losses = jax.lax.scan(
                one, (params, opt_state), length=local_steps)
            loss = losses[-1]
        return params, opt_state, loss

    return step


def make_local_eval_step(task) -> Callable:
    """Returns ``eval(params, x, y, mask) -> statistics`` of the task
    (fedtpu.training.task): for classification the ``(K, K)`` confusion
    matrix of the argmax predictions."""
    return task.stats
