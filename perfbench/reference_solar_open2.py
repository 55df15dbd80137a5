"""The plain reference of the Solar-Open2 cell: the stack and FedAvg with
server momentum, in straight ``jax.numpy`` and float32 at ``highest`` matmul
precision, independent of ``fedtpu/``.

The stack (upstage/Solar-Open2-250B, ``config.json``, ``model_type:
solar_open2``): every layer is ``h + Mixer(RMSNorm(h))``, then ``h +
Experts(RMSNorm(h))`` (``first_k_dense_replace`` 0); no positions anywhere
(``use_rope`` false); a final RMSNorm and an untied head.

* **Gated grouped-query attention** (the layers of ``gqa_layers``; a mixer
  that has ``k``): ``num_attention_heads`` query heads of ``head_dim`` over
  ``num_key_value_heads`` key-value heads (query head ``i`` reads key-value
  head ``i // (heads / kv heads)``), ``softmax(q k^T head_dim^-1/2)`` over a
  document's earlier tokens and itself, whole ``(T, T)`` scores a few heads
  at a time, nothing rotated; ``use_gqa_gate``: ``out = W_o [ctx *
  sigmoid(x W_g)]``, elementwise, no bias (arXiv:2505.06708's head-specific
  sigmoid gate after the core).
* **KDA** (every other layer; a mixer that has ``q_conv``): as
  ``reference_kimi_linear`` writes it (its ``short_conv`` and its
  token-by-token ``kda_recurrence`` ARE this file's), with one change,
  ``kda_allow_neg_eigval``: ``beta = 2 sigmoid(x W_b)``, so ``I - beta k
  k^T`` has eigenvalues in (-1, 1]. ``S_t = (I - beta_t k_t k_t^T) Diag(exp
  g_t) S_{t-1} + beta_t k_t v_t^T``, zero first at a document's first token;
  ``o_t = S_t^T q_t``; then ``W_o [w * RMSNorm_head(o) * sigmoid(x W_g1 W_g2
  + b_g)]``.
* **Experts**: ``s = sigmoid(x W_r)`` over all ``n_routed_experts``, the top
  ``num_experts_per_tok`` of ``s + bias``, weights ``routed_scaling_factor *
  s / (sum s + 1e-20)`` (``norm_topk_prob``), the held experts computed
  DENSELY, and one shared expert (``reference_kimi_linear.experts``, handed
  this config's keys under the names it reads).

Departures from the published model, each an input or a statement of the
configuration and none of the mathematics: the depth and which layer is of
which kind are the parameters handed in; the weights are random; **the
share**: the parameters hold ``num_attention_heads`` of the published query
heads with the ``num_key_value_heads`` they read and
``linear_attn_config.num_heads`` KDA heads (``W_o``'s rows for the other
heads and what they would add are left out), experts ``[first_expert,
first_expert + held)`` of every expert layer and a slice of the vocabulary;
the router still scores all its experts; no auxiliary or balancing loss and
the selection bias is never updated; a row is a packed sequence whose
segments are documents: state, convolution and attention restart at a
document's first token, padding (segment 0) and each document's last token
are out of the loss. What the config leaves to the code is listed under
``assumed`` in the configuration's file.

FedAvg with server momentum as ``reference_kimi_linear.fedavgm_rounds``
writes it, its sums made on the host (``fedavgm_rounds``).
"""

from __future__ import annotations

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference_kimi_linear as base
from perfbench.reference_kimi_linear import (HEAD_BLOCK, L2_EPS,
                                             STEP_COMPILER_OPTIONS, _rms,
                                             _starts, exits, kda_recurrence,
                                             short_conv)


def _mm(a, b):
    """Every large matrix product of this file's mixers goes through here."""
    return a @ b


# -------------------------------------------------------------------- KDA
def step_size(layer, x, cfg):
    """``beta (T, heads)``: the delta rule's step."""
    beta = jax.nn.sigmoid(_mm(x, layer["b_proj"]))
    return 2.0 * beta if cfg["kda_allow_neg_eigval"] else beta


def kda(layer, x, segs, cfg):
    """The KDA mixer on the normed input ``x (T, C)``."""
    lin = cfg["linear_attn_config"]
    t, heads, d = x.shape[0], lin["num_heads"], lin["head_dim"]
    starts = _starts(segs)
    q, k, v = (jax.nn.silu(short_conv(_mm(x, layer[f"{n}_proj"]),
                                      layer[f"{n}_conv"], starts))
               .reshape(t, heads, d) for n in "qkv")
    normed = lambda a: a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + L2_EPS)
    q, k = normed(q) * d ** -0.5, normed(k)
    g = -jnp.exp(layer["A_log"])[:, None] * jax.nn.softplus(
        (_mm(_mm(x, layer["f_a"]), layer["f_b"]) + layer["dt_bias"]).reshape(
            t, heads, d))
    o = kda_recurrence(q, k, v, g, step_size(layer, x, cfg), starts)
    gate = (_mm(_mm(x, layer["g_a"]), layer["g_b"]) + layer["g_bias"]).reshape(
        t, heads, d)
    y = _rms(o, layer["o_norm"], cfg["rms_norm_eps"]) * jax.nn.sigmoid(gate)
    return _mm(y.reshape(t, heads * d), layer["o_proj"])


# ------------------------------------------- gated grouped-query attention
def context_gate(layer, x, cfg):
    """``sigmoid(x W_g) (T, heads * head_dim)``, or 1 without the gate."""
    if not cfg["use_gqa_gate"]:
        return 1.0
    return jax.nn.sigmoid(_mm(x, layer["gate"]))


def attention(layer, x, segs, cfg):
    """Grouped-query softmax attention without positions on the normed input
    ``x (T, C)``, its context gated before ``W_o``."""
    t, heads, kv = x.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    q = _mm(x, layer["q"]).reshape(t, heads, hd)
    k, v = (jnp.repeat(_mm(x, layer[n]).reshape(t, kv, hd), heads // kv, axis=1)
            for n in "kv")
    idx = jnp.arange(t)
    allowed = (idx[:, None] >= idx[None]) & (segs[:, None] == segs[None])

    @jax.checkpoint     # a block's (heads, T, T) scores are recomputed in
    def some(qkv):       # the backward pass, not kept: memory only
        qh, kh, vh = qkv                                    # (block, T, d)
        scores = _mm(qh, kh.swapaxes(-1, -2)) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -1e30), axis=-1)
        return _mm(probs, vh)

    block = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads
    blocks = lambda a: a.reshape(t, heads // block, block, -1).transpose(1, 2, 0, 3)
    ctx = jax.lax.map(some, (blocks(q), blocks(k), blocks(v)))
    ctx = ctx.transpose(2, 0, 1, 3).reshape(t, heads * hd)
    return _mm(ctx * context_gate(layer, x, cfg), layer["o"])


# ---------------------------------------------------------------- experts
def experts(layer, x, cfg):
    """The held experts' part of the routed sum and the shared expert:
    ``reference_kimi_linear.experts`` under the names it reads."""
    return base.experts(layer, x, {
        "num_experts_per_token": cfg["num_experts_per_tok"],
        "moe_renormalize": cfg["norm_topk_prob"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "first_expert": cfg.get("first_expert", 0)})


# -------------------------------------------------------------- the model
def kind_of(part) -> str:
    """What its leaves make a sublayer: ``"kda"``, ``"gqa"`` or
    ``"experts"``."""
    for leaf, kind in (("q_conv", "kda"), ("router", "experts")):
        if leaf in part:
            return kind
    return "gqa"


def sublayer(part, h, segs, cfg):
    """``h + F(RMSNorm(h))`` for the mixer or the feed-forward ``part``."""
    x = _rms(h, part["norm"], cfg["rms_norm_eps"])
    kind = kind_of(part)
    if kind == "kda":
        return h + kda(part, x, segs, cfg)
    if kind == "gqa":
        return h + attention(part, x, segs, cfg)
    return h + experts(part, x, cfg)


def block(layer, h, segs, cfg):
    """One layer on ``h (T, C)``: its mixer, then its experts."""
    return sublayer(layer["ffn"], sublayer(layer["mixer"], h, segs, cfg), segs,
                    cfg)


def mean_loss(params, row, cfg):
    """The mean next-token loss of one packed row ``(2, T)`` (tokens and
    segment ids), and its two sums: the whole model as one function."""
    # a layer's intermediates are recomputed in the backward pass, not kept
    # for the whole depth: memory only
    run = jax.checkpoint(functools.partial(block, segs=row[1], cfg=cfg))
    h = params["embed"][row[0]]
    for layer in params["layers"]:
        h = run(layer, h)
    return exits(params["final_norm"], params["head"], h, row, cfg)


def compiled_step(params, row, cfg: dict, learning_rate: float):
    """``reference_kimi_linear.compiled_step`` for this file's sublayers: one
    SGD step on ``mean_loss`` of one packed row, compiled from shapes alone,
    ``step(p, row) -> (p - lr grad, loss, (summed loss, count))``, run a
    SUBLAYER AT A TIME (the forward pass keeps each sublayer's input; the
    backward pass walks them in reverse, each one's ``jax.vjp`` giving its
    leaves' gradient, applied there, and its input's cotangent); the
    sublayers of a kind (three: the three KDA mixers and the four expert
    layers compile once each) are one compiled function. The same step as
    ``jax.grad(mean_loss)``: a self-test holds them equal."""
    frozen = dict(cfg)
    where = getattr(row, "sharding", None)      # a described device's, or none
    spec = lambda a, dtype=jnp.float32: jax.ShapeDtypeStruct(
        a.shape, dtype, sharding=where)
    shapes = jax.tree.map(spec, params)
    tokens = segs = jax.ShapeDtypeStruct(row.shape[1:], jnp.int32,
                                         sharding=where)
    rows = jax.ShapeDtypeStruct(row.shape, jnp.int32, sharding=where)
    sgd = lambda leaves, grads: jax.tree.map(
        lambda a, b: a - learning_rate * b, leaves, grads)
    parts = lambda p: [layer[name] for layer in p["layers"]
                       for name in ("mixer", "ffn")]

    def sublayer_back(part, h, segs, g):
        _, pull = jax.vjp(lambda l, a: sublayer(l, a, segs, frozen), part, h)
        g_part, g_h = pull(g)
        return sgd(part, g_part), g_h

    def exits_back(final_norm, head, h, row):
        (loss, sums), grads = jax.value_and_grad(
            lambda *a: exits(*a, row, frozen), argnums=(0, 1, 2),
            has_aux=True)(final_norm, head, h)
        return loss, sums, grads

    def enter_back(embed, tokens, g):
        _, pull = jax.vjp(lambda e: e[tokens], embed)
        return sgd(embed, pull(g)[0])

    h_spec = jax.ShapeDtypeStruct((row.shape[-1], params["embed"].shape[1]),
                                  jnp.float32, sharding=where)
    kinds = {}
    for part in parts(shapes):
        kinds.setdefault(kind_of(part), part)

    def compile_(fn, *a, donate=()):
        """``fn`` compiled for arguments shaped as ``a``, on a thread of the
        pool: the pieces compile side by side."""
        def work():
            with jax.default_matmul_precision("highest"):   # a thread's own
                return jax.jit(fn, donate_argnums=donate).lower(*a).compile(
                    compiler_options=STEP_COMPILER_OPTIONS)
        return pool.submit(work)

    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
        # the longest first
        backward = {k: compile_(sublayer_back, v, h_spec, segs, h_spec,
                                donate=(0, 3)) for k, v in kinds.items()}
        forward = {k: compile_(lambda l, a, s: sublayer(l, a, s, frozen), v,
                               h_spec, segs) for k, v in kinds.items()}
        go_out = compile_(exits_back, shapes["final_norm"], shapes["head"],
                          h_spec, rows)
        go_in = compile_(lambda e, t: e[t], shapes["embed"], tokens)
        come_back = compile_(enter_back, shapes["embed"], tokens, h_spec,
                             donate=(0,))
        backward = {k: v.result() for k, v in backward.items()}
        forward = {k: v.result() for k, v in forward.items()}
        go_out, go_in, come_back = (go_out.result(), go_in.result(),
                                    come_back.result())
    apply = jax.jit(sgd, donate_argnums=(0,))

    def step(p, row):
        tokens, segs = row[0], row[1]
        hs = [go_in(p["embed"], tokens)]
        for part in parts(p):
            hs.append(forward[kind_of(part)](part, hs[-1], segs))
        loss, sums, (g_final, g_head, g_h) = go_out(
            p["final_norm"], p["head"], hs.pop(), row)
        new = []
        for part in reversed(parts(p)):
            part, g_h = backward[kind_of(part)](part, hs.pop(), segs, g_h)
            new.append(part)
        new.reverse()
        return ({"embed": come_back(p["embed"], tokens, g_h),
                 "layers": tuple({"mixer": m, "ffn": f}
                                 for m, f in zip(new[::2], new[1::2])),
                 "final_norm": apply(p["final_norm"], g_final),
                 "head": apply(p["head"], g_head)}, loss, sums)

    return step


def fedavgm_rounds(init_params, client_rows, rounds: int, cfg: dict,
                   learning_rate: float, momentum: float = 0.9,
                   server_lr: float = 1.0, step=None):
    """``rounds`` rounds from the global ``init_params`` (arrays, or a
    function of no argument that makes them) over ``client_rows`` (a list,
    one ``(n_c, 2, T)`` int32 array a client). Returns ``(losses (rounds,
    C), global parameters after the last round, on the host)``. FedAvg with
    server momentum as ``reference_kimi_linear.fedavgm_rounds`` writes it:
    every client in turn starts from the global model and runs one epoch of
    one-sequence SGD steps (``step``: ``compiled_step``'s, compiled here
    from shapes alone where none is handed in); the server takes the mean of
    the clients' parameters weighted by the tokens each counted in its loss,
    less the global, and applies it with momentum (``m = beta m + delta``,
    ``g += lr m``); a client's loss of a round is the mean of its steps'
    losses weighted by those tokens.

    **The device holds ONE client's copy** and (inside a step) a sublayer's
    gradient: the global, the momentum AND the round's weighted sum wait on
    the host, in numpy and float32 (three copies at the most: the global,
    the sum that becomes the momentum, the new global), and the server's
    update is made there.
    At 905.8M parameters three copies on the device beside a step's
    temporaries read 12.98 to 15.46 GB by the run, over the round program's
    own 13.46 (my chip runs, PR 47): the run's peak of memory, which
    ``peak_hbm_mb`` reports, has to be the program's and not its
    reference's."""
    make = init_params if callable(init_params) else lambda: init_params
    if step is None:
        step = compiled_step(jax.eval_shape(make), client_rows[0][0], cfg,
                             learning_rate)
    f32 = np.float32
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
        def leaves(fn, *trees):
            """``fn`` of the trees' leaves, a leaf a task: numpy lets go of
            the interpreter's lock, and one thread over 3.6 GB a pass was
            50 s of a run's set-up."""
            shape = jax.tree.structure(trees[0])
            return jax.tree.unflatten(shape, list(pool.map(
                fn, *map(jax.tree.leaves, trees))))

        # never written in place: a caller's arrays stay what they were
        g = jax.tree.map(lambda a: np.asarray(a, f32), make())
        m, out = None, []
        for _ in range(rounds):
            acc, losses, total = None, [], 0.0
            for rows in client_rows:
                p, steps = jax.device_put(g), []
                for row in rows:
                    p, loss, sums = step(p, jnp.asarray(row, jnp.int32))
                    steps.append((loss, sums[1]))
                loss, count = np.asarray(jax.device_get(steps), np.float64).T
                counted = float(count.sum())
                # the host holds one sum, not a copy a client
                if acc is None:
                    acc = leaves(lambda a: f32(counted) * np.asarray(a), p)
                else:
                    leaves(lambda s, a: np.add(s, f32(counted) * np.asarray(a),
                                               out=s), acc, p)
                del p
                total += counted
                losses.append((loss * count).sum() / max(counted, 1.0))

            def to_delta(s, c):     # the sum becomes the mean delta in place
                s /= f32(total)
                s -= c
                return s

            leaves(to_delta, acc, g)
            if m is None:
                m = acc
            else:
                leaves(lambda a, d: np.add(np.multiply(a, f32(momentum), out=a),
                                           d, out=a), m, acc)
            del acc
            g = leaves(lambda c, b: c + f32(server_lr) * b, g, m)
            out.append(losses)
    return np.asarray(out, np.float64), g
