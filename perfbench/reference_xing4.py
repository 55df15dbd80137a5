"""The plain reference of the Xing4.0 cell: the stack and FedAvg with server
momentum, in straight ``jax.numpy`` and float32 at ``highest`` matmul
precision, independent of ``fedtpu/``.

The stack (XingChen-AGI/Xing4.0-29B-A4B, ``config.json``, ``model_type:
xing4_0``), a token's state being ``X (n, C)``, ``n = hc_mult`` streams, the
token's embedding repeated ``n`` times on the way in:

* **the residual path** around EVERY sublayer ``F`` (mHC, arXiv:2512.24880).
  ``x = flatten(X)`` (stream-major), ``x' = x / sqrt(mean(x^2) + eps)`` with
  no gain; ``H_pre = sigmoid(a_pre (x' phi_pre) + b_pre)`` (n), ``H_post =
  2 sigmoid(a_post (x' phi_post) + b_post)`` (n), ``H_res = SK(a_res mat(x'
  phi_res) + b_res)`` (n x n, row-major), ``SK``: ``M = exp(clip(., min,
  max))``, then ``hc_sinkhorn_iters`` times ``M <- M / (colsum(M) +
  hc_eps)``, ``M <- M / (rowsum(M) + hc_eps)``, a Python loop.
  ``u = H_pre X``, ``y = F(RMSNorm(u; w))``, ``X <- H_res X + H_post^T y``.
  After the last layer ``h = sum over streams``. A module's leaves are
  ``phi (n (n + 2), n C)``, a row a logit (``n`` of ``H_pre``, ``n`` of
  ``H_post``, ``n n`` of ``H_res``), ``alpha (3,)`` and ``bias (n (n +
  2),)``.
* **latent attention** (``transformers``' ``DeepseekV3Attention``): ``c_q =
  RMSNorm(x W_qa)``, ``q = c_q W_qb`` in heads of ``nope | rope`` columns;
  ``[c_kv | k_r] = x W_kva``, ``c_kv <- RMSNorm(c_kv)``, ``[k_n | v] = c_kv
  W_kvb`` in heads of ``nope | v`` columns; RoPE on ``q``'s last ``rope``
  columns and on ``k_r``, which all heads share, pairs of NEIGHBOURING
  columns turned by ``position * frequency`` (``rope_interleave``; the
  family's code moves the pairs apart first and rotates halves, which
  permutes q and k alike and changes no score), YaRN's frequencies;
  ``softmax(q k^T (nope + rope)^-1/2 mscale^2)``, causal; whole ``(T, T)``
  scores a head at a time.
* **feed-forward**: a leading layer is ``W_down(silu(W_gate x) * W_up x)``;
  an expert layer scores ``s = sigmoid(x W_r)`` over all experts, chooses
  the top ``num_experts_per_tok`` of ``s + bias``, weighs them ``scale *
  s / (sum s + 1e-20)`` (``DeepseekV3TopkRouter`` with one group) and adds
  one shared expert; every expert is the gated form; the held experts are
  computed DENSELY, every one on every token, weighted by the gate and zero
  elsewhere.
* **multi-token prediction** (DeepSeek-V3, arXiv:2412.19437 section 2.2, one
  module): ``h'_i = [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))] M``, ``h`` the
  main stack's summed streams BEFORE its final norm; one more block of the
  expert kind on streams started from ``h'`` repeated; its own final norm;
  the shared embedding and head; it predicts ``t_{i+2}``. ``loss = L_main +
  lambda L_mtp``, each a mean over its own valid positions. The
  ``transformers`` code builds no such module: this is written from the
  paper.

Departures from the published model, each an input or a statement of the
configuration and none of the mathematics: the depth and the number of
leading dense layers are the parameters handed in; the weights are random;
**the share**: the parameters hold experts ``[first_expert, first_expert +
held)`` of every expert layer and a slice of the vocabulary, the router still
scores all its experts, and what the absent experts would have added is left
out; no auxiliary or balancing loss and the selection bias is never updated;
a row is a packed sequence whose segments are documents: attention stays
within a document and its positions restart there, padding (segment 0) and
each document's last token are out of the main loss, its last two out of the
module's, and ``t_{i+1}`` is read from the same document. What the
``xing4_0`` modelling code (not public here) may do otherwise is listed under
``assumed`` in the configuration's file.

FedAvg with server momentum as ``reference_lm.py`` writes it (its own copy),
with the two parts of a client's loss kept apart.
"""

from __future__ import annotations

import concurrent.futures
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Heads whose whole (T, T) scores exist at one time.
HEAD_BLOCK = 4


def _mm(a, b):
    """Every large matrix product of the stack goes through here."""
    return a @ b


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


# ------------------------------------------------------ the residual path
def sinkhorn(logits, cfg):
    """``(T, n, n)`` logits to ``H_res``: a Python loop over the iterations."""
    m = jnp.exp(jnp.clip(logits, cfg["mhc_h_res_clamp_min"],
                         cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (m.sum(axis=1, keepdims=True) + cfg["hc_eps"])     # columns
        m = m / (m.sum(axis=2, keepdims=True) + cfg["hc_eps"])     # rows
    return m


def hyper_maps(x, module, cfg):
    """``(H_pre (T, n), H_post (T, n), H_res (T, n, n))`` from the streams
    ``x (T, n, C)``."""
    t, n, _ = x.shape
    flat = x.reshape(t, -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                                + cfg["rms_norm_eps"])
    logits = flat @ module["phi"].T                 # float32 by statement
    a_pre, a_post, a_res = module["alpha"]
    b = module["bias"]
    pre = jax.nn.sigmoid(a_pre * logits[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a_post * logits[:, n:2 * n] + b[n:2 * n])
    res = sinkhorn(a_res * logits[:, 2 * n:].reshape(t, n, n)
                   + b[2 * n:].reshape(n, n), cfg)
    return pre, post, res


def sublayer(x, module, fn, cfg):
    """``X <- H_res X + H_post^T F(H_pre X)`` on ``x (T, n, C)``: float32
    products summed over the source streams (no matrix unit: an ``einsum``
    here runs as thousands of 4 x 4 products at 'highest' precision, most of
    a step's seconds)."""
    pre, post, res = hyper_maps(x, module, cfg)
    y = fn((pre[:, :, None] * x).sum(axis=1))
    return ((res[:, :, :, None] * x[:, None, :, :]).sum(axis=2)
            + post[:, :, None] * y[:, None, :])


# ------------------------------------------------------- latent attention
def yarn_inv_freq(cfg) -> np.ndarray:
    """``_compute_yarn_parameters``' frequencies for the rotary columns."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    scaling = cfg["rope_scaling"]
    factor, original = scaling["factor"], scaling["original_max_position_embeddings"]
    find = lambda rotations: (dim * math.log(original / (rotations * 2 * math.pi))
                              / (2 * math.log(base)))
    low = max(math.floor(find(scaling["beta_fast"])), 0)
    high = min(math.ceil(find(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low),
                   0, 1)
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    return ((1.0 / (factor * freqs)) * ramp
            + (1.0 / freqs) * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg["rope_scaling"]
    if scaling.get("mscale_all_dim") and scaling["factor"] > 1:
        mscale = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0
        scale *= mscale * mscale
    return scale


def rope_neighbours(x, pos, inv):
    """Pairs ``(x[2i], x[2i + 1])`` of the last axis turned by ``pos *
    inv[i]``; ``x (T, ..., d)``, ``pos (T,)``."""
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def positions(segs):
    """A token's position within its document."""
    idx = jnp.arange(segs.shape[0])
    starts = jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])
    return idx - jax.lax.cummax(jnp.where(starts, idx, 0))


def attention(layer, x, segs, cfg):
    """Latent attention on the normed input ``x (T, C)``."""
    t, heads = x.shape[0], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    pos, inv = positions(segs), jnp.asarray(yarn_inv_freq(cfg))
    q = _mm(_rms(_mm(x, layer["q_a"]), layer["q_a_norm"], eps),
            layer["q_b"]).reshape(t, heads, nope + rope)
    kv = _mm(x, layer["kv_a"])
    rank = cfg["kv_lora_rank"]
    k_r = rope_neighbours(kv[:, rank:], pos, inv)                   # (T, rope)
    kv = _mm(_rms(kv[:, :rank], layer["kv_a_norm"], eps),
             layer["kv_b"]).reshape(t, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope],
                         rope_neighbours(q[..., nope:], pos, inv)], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r[:, None], (t, heads, rope))],
                        axis=-1)
    v = kv[..., nope:]
    idx = jnp.arange(t)
    allowed = (idx[:, None] >= idx[None]) & (segs[:, None] == segs[None])
    scale = softmax_scale(cfg)

    @jax.checkpoint     # a block's (heads, T, T) scores are recomputed in
    def some(qkv):       # the backward pass, not kept: memory only
        qh, kh, vh = qkv                                    # (block, T, d)
        scores = _mm(qh, kh.swapaxes(-1, -2)) * scale
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -1e30), axis=-1)
        return _mm(probs, vh)

    block = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads
    blocks = lambda a: a.reshape(t, heads // block, block, -1).transpose(1, 2, 0, 3)
    ctx = jax.lax.map(some, (blocks(q), blocks(k), blocks(v)))
    return _mm(ctx.transpose(2, 0, 1, 3).reshape(t, heads * vd), layer["o"])


# ----------------------------------------------------------- feed-forward
def gated(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def gate_weights(x, router, bias, top_k: int, norm_topk_prob: bool,
                 scale: float):
    """``(T, E)``: an expert's weight where it is among the token's chosen,
    zero elsewhere."""
    scores = jax.nn.sigmoid(x @ router)             # float32 by statement
    choice = scores + bias
    kth = jnp.sort(choice, axis=-1)[:, -top_k][:, None]
    w = jnp.where(choice >= kth, scores, 0.0)
    if norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * scale


def experts(layer, x, cfg):
    """The held experts' part of the routed sum, densely, and the shared
    expert; ``layer["up"]`` holds experts ``[first_expert, first_expert +
    held)`` of the ``router``'s."""
    w = gate_weights(x, layer["router"], layer["router_bias"],
                     cfg["num_experts_per_tok"], cfg["norm_topk_prob"],
                     cfg["routed_scaling_factor"])
    first, (held, hidden, width) = cfg.get("first_expert", 0), layer["up"].shape
    side_by_side = lambda a: a.transpose(1, 0, 2).reshape(hidden, held * width)
    act = (jax.nn.silu(_mm(x, side_by_side(layer["gate"])))
           * _mm(x, side_by_side(layer["up"])))
    act = act * jnp.repeat(w[:, first:first + held], width, axis=1)
    routed = _mm(act, layer["down"].reshape(held * width, hidden))
    return routed + gated(x, layer["shared_gate"], layer["shared_up"],
                          layer["shared_down"])


# -------------------------------------------------------------- the model
def block(layer, x, segs, cfg):
    """One layer on the streams ``x (T, n, C)``: attention, then the
    feed-forward its leaves make it (a router: experts; none: dense)."""
    eps = cfg["rms_norm_eps"]
    attn, ffn = layer["attn"], layer["ffn"]
    x = sublayer(x, layer["attn_hc"],
                 lambda u: attention(attn, _rms(u, attn["norm"], eps), segs,
                                     cfg), cfg)

    def feed_forward(u):
        u = _rms(u, ffn["norm"], eps)
        if "router" in ffn:
            return experts(ffn, u, cfg)
        return gated(u, ffn["gate"], ffn["up"], ffn["down"])

    return sublayer(x, layer["ffn_hc"], feed_forward, cfg)


def _ahead(a, by):
    return jnp.concatenate([a[by:], jnp.zeros((by,), a.dtype)])


def _summed_loss(h, head, labels, valid):
    logp = jax.nn.log_softmax(_mm(h, head), axis=-1)    # whole (T, vocab)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -(ll * valid).sum()


def enter(embed, tokens, cfg):
    """The streams on the way in: the token's embedding, ``n`` times."""
    return jnp.repeat(embed[tokens][:, None, :], cfg["hc_mult"], axis=1)


def module_enter(module, embed, x, tokens, cfg):
    """A prediction module's streams on the way in, from the main stack's
    last streams ``x``: ``[RMSNorm(h) ; RMSNorm(Emb(t_{i+1}))] M`` repeated."""
    eps = cfg["rms_norm_eps"]
    both = jnp.concatenate(
        [_rms(x.sum(axis=1), module["h_norm"], eps),
         _rms(embed[_ahead(tokens, 1)], module["e_norm"], eps)], axis=-1)
    return jnp.repeat(_mm(both, module["proj"])[:, None, :], cfg["hc_mult"],
                      axis=1)


def exits(final_norm, head, module_norms, x, module_xs, row, cfg):
    """``(L_main + lambda L_mtp, (main summed loss, main count, module
    summed loss, module count))`` from the main stack's last streams ``x``
    and each module's (``module_xs``, with their final norms): the streams
    summed, a final norm, the shared head, whole logits. Without a module
    the last two sums are zero."""
    tokens, segs = row[0], row[1]
    eps = cfg["rms_norm_eps"]
    real, next_same = segs > 0, _ahead(segs, 1) == segs
    valid = (real & next_same).astype(jnp.float32)
    main = _summed_loss(_rms(x.sum(axis=1), final_norm, eps), head,
                        _ahead(tokens, 1), valid)
    loss = main / jnp.maximum(valid.sum(), 1.0)
    extra, extra_count = jnp.float32(0.0), jnp.float32(0.0)
    for norm, xm in zip(module_norms, module_xs):
        valid2 = (real & next_same & (_ahead(segs, 2) == segs)).astype(
            jnp.float32)
        extra = _summed_loss(_rms(xm.sum(axis=1), norm, eps), head,
                             _ahead(tokens, 2), valid2)
        extra_count = valid2.sum()
        loss = loss + cfg["mtp_loss_weight"] * extra / jnp.maximum(
            extra_count, 1.0)
    return loss, (main, valid.sum(), extra, extra_count)


def _module_parts(params):
    """A module's leaves apart: what opens it, its block, its final norm."""
    return ([{k: m[k] for k in ("h_norm", "e_norm", "proj")}
             for m in params["mtp"]],
            [m["block"] for m in params["mtp"]],
            [m["final_norm"] for m in params["mtp"]])


def mean_loss(params, row, cfg):
    """``L_main + lambda L_mtp`` of one packed row ``(2, T)`` (tokens and
    segment ids), and the four sums: the whole model as one function."""
    tokens, segs = row[0], row[1]
    # a layer's intermediates are recomputed in the backward pass, not kept
    # for the whole depth: memory only
    run = jax.checkpoint(functools.partial(block, segs=segs, cfg=cfg))
    x = enter(params["embed"], tokens, cfg)
    for layer in (*params["dense"], *params["experts"]):
        x = run(layer, x)
    opens, blocks, norms = _module_parts(params)
    module_xs = [run(b, module_enter(o, params["embed"], x, tokens, cfg))
                 for o, b in zip(opens, blocks)]
    return exits(params["final_norm"], params["head"], norms, x, module_xs,
                 row, cfg)


def sequence_losses(params, row, cfg):
    """``(main summed loss, main count, module summed loss, module count)``."""
    return mean_loss(params, row, cfg)[1]


def compiled_step(params, row, cfg: dict, learning_rate: float):
    """One SGD step on ``mean_loss`` of one packed row, compiled from shapes
    alone (``params`` and ``row`` may be ``ShapeDtypeStruct``s): ``step(p,
    row) -> (p - lr grad, loss, (main, count, module, count))``; ``p`` is
    used up.

    The same step as ``jax.grad(mean_loss)`` (a self-test holds them
    equal), run a BLOCK AT A TIME: the forward pass keeps each block's
    input; the backward pass walks the blocks in reverse, each block's
    ``jax.vjp`` giving its leaves' gradient, applied there, and its input's
    cotangent. The blocks of a kind are one compiled function (five expert
    blocks, the module's among them, and the dense one): a third of the
    whole step's compile, which a run of the cell pays before its first
    round. Nothing else differs: the pieces are ``enter``, ``block``,
    ``module_enter`` and ``exits``, what ``mean_loss`` is made of."""
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

    def block_back(layer, x, segs, g):
        _, pull = jax.vjp(lambda l, a: block(l, a, segs, frozen), layer, x)
        g_layer, g_x = pull(g)
        return sgd(layer, g_layer), g_x

    def exits_back(final_norm, head, norms, x, module_xs, row):
        (loss, sums), grads = jax.value_and_grad(
            lambda *a: exits(*a, row, frozen), argnums=(0, 1, 2, 3, 4),
            has_aux=True)(final_norm, head, norms, x, module_xs)
        return loss, sums, grads

    def module_enter_back(module, embed, x, tokens, g):
        _, pull = jax.vjp(lambda m, e, a: module_enter(m, e, a, tokens, frozen),
                          module, embed, x)
        g_module, g_embed, g_x = pull(g)
        return sgd(module, g_module), g_embed, g_x

    def enter_back(embed, tokens, g, others):
        _, pull = jax.vjp(lambda e: enter(e, tokens, frozen), embed)
        return sgd(embed, sum(others, pull(g)[0]))

    x_spec = jax.eval_shape(lambda e, t: enter(e, t, frozen), shapes["embed"],
                            tokens)
    x_spec = spec(x_spec)
    opens, blocks, norms = _module_parts(shapes)
    def compile_(fn, *a, donate=()):
        """``fn`` compiled for arguments shaped as ``a``, on a thread of the
        pool: the pieces compile side by side."""
        def work():
            with jax.default_matmul_precision("highest"):   # a thread's own
                return jax.jit(fn, donate_argnums=donate).lower(*a).compile()
        return pool.submit(work)

    kinds = {"dense": shapes["dense"], "experts": (*shapes["experts"], *blocks)}
    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as pool:
        # the longest first
        backward = {k: compile_(block_back, v[0], x_spec, segs, x_spec,
                                donate=(0, 3)) for k, v in kinds.items() if v}
        forward = {k: compile_(lambda l, a, s: block(l, a, s, frozen), v[0],
                               x_spec, segs) for k, v in kinds.items() if v}
        go_out = compile_(exits_back, shapes["final_norm"], shapes["head"],
                          norms, x_spec, [x_spec] * len(norms), rows)
        if opens:
            module_in = compile_(
                lambda m, e, a, t: module_enter(m, e, a, t, frozen), opens[0],
                shapes["embed"], x_spec, tokens)
            module_back = compile_(module_enter_back, opens[0],
                                   shapes["embed"], x_spec, tokens, x_spec,
                                   donate=(0, 4))
            module_in, module_back = module_in.result(), module_back.result()
        go_in = compile_(lambda e, t: enter(e, t, frozen), shapes["embed"],
                         tokens).result()
        come_back = compile_(enter_back, shapes["embed"], tokens, x_spec,
                             [shapes["embed"]] * len(opens),
                             donate=(0,)).result()
        backward = {k: v.result() for k, v in backward.items()}
        forward = {k: v.result() for k, v in forward.items()}
        go_out = go_out.result()
    apply = jax.jit(sgd, donate_argnums=(0,))

    def step(p, row):
        tokens, segs = row[0], row[1]
        stack = [("dense", layer) for layer in p["dense"]]
        stack += [("experts", layer) for layer in p["experts"]]
        xs = [go_in(p["embed"], tokens)]
        for kind, layer in stack:
            xs.append(forward[kind](layer, xs[-1], segs))
        opens, blocks, norms = _module_parts(p)
        module_ins = [module_in(o, p["embed"], xs[-1], tokens) for o in opens]
        module_xs = [forward["experts"](b, a, segs)
                     for b, a in zip(blocks, module_ins)]
        loss, sums, (g_final, g_head, g_norms, g_x, g_module_xs) = go_out(
            p["final_norm"], p["head"], norms, xs[-1], module_xs, row)
        del module_xs
        modules, embed_grads = [], []
        for o, b, a, g, norm, g_norm in zip(opens, blocks, module_ins,
                                            g_module_xs, norms, g_norms):
            b, g = backward["experts"](b, a, segs, g)
            o, g_embed, g_more = module_back(o, p["embed"], xs[-1], tokens, g)
            g_x = g_x + g_more
            embed_grads.append(g_embed)
            modules.append({**o, "block": b,
                            "final_norm": apply(norm, g_norm)})
        layers = []
        for kind, layer in reversed(stack):
            x = xs.pop()
            layer, g_x = backward[kind](layer, xs[-1], segs, g_x)
            layers.append((kind, layer))
        layers.reverse()
        new = {"embed": come_back(p["embed"], tokens, g_x, embed_grads),
               "dense": tuple(l for k, l in layers if k == "dense"),
               "experts": tuple(l for k, l in layers if k == "experts"),
               "final_norm": apply(p["final_norm"], g_final),
               "head": apply(p["head"], g_head), "mtp": tuple(modules)}
        return new, loss, sums

    return step


def fedavgm_rounds(init_params, client_rows, rounds: int, cfg: dict,
                   learning_rate: float, momentum: float = 0.9,
                   server_lr: float = 1.0, step=None):
    """``rounds`` rounds from the global ``init_params`` (arrays, or a
    function of no argument that makes them on the device) over
    ``client_rows`` (a list, one ``(n_c, 2, T)`` int32 array a client).
    Returns ``({"loss", "main", "mtp"}: each (rounds, C), global parameters
    after the last round, on the host)``. Every client in turn starts from
    the global model and runs one epoch of one-sequence SGD steps on ``L_main
    + lambda L_mtp``; the server takes the mean of the clients' deltas
    weighted by the tokens each counted in its MAIN loss and applies it with
    momentum (``m = beta m + delta``, ``g += lr m``). A client's ``loss`` of
    a round is the mean of its steps' losses weighted by those tokens, each
    at the parameters the step started from; ``main`` and ``mtp`` are each
    part's summed loss over the part's own valid positions.

    The device holds one client's copy, the round's weighted sum of the
    clients' parameters and (inside a step) a block's gradient; the global
    model and the server's momentum wait on the HOST while the clients
    train, so that a step of 4,096 tokens has the room it needs beside them
    and the run's peak of memory stays the round program's, not this
    reference's. The mean delta is therefore ``sum(w p) / W - global``, not
    ``sum(w (p - global)) / W``: the same number to a few units in the last
    place of a parameter, which is where the sum ``global + step`` rounds
    anyway. ``step`` is ``compiled_step``'s, compiled here from shapes alone
    where none is handed in."""
    make = init_params if callable(init_params) else lambda: init_params
    host = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32), tree)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add_weighted(acc, p, w):
        return jax.tree.map(lambda a, b: a + w * b, acc, p)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def server(g, m, acc, total):
        m = jax.tree.map(lambda a, b, c: momentum * a + (b / total - c),
                         m, acc, g)
        return jax.tree.map(lambda a, b: a + server_lr * b, g, m), m

    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    if step is None:
        step = compiled_step(jax.eval_shape(make), client_rows[0][0], cfg,
                             learning_rate)
    g = host(make())
    m, out = None, {"loss": [], "main": [], "mtp": []}
    for r in range(rounds):
        acc = None
        rows_of, total = {name: [] for name in out}, 0.0
        for rows in client_rows:
            p, steps = jax.device_put(g), []
            acc = zeros(p) if acc is None else acc
            for row in rows:
                p, loss, sums = step(p, jnp.asarray(row, jnp.int32))
                steps.append((loss, *sums))
            steps = np.asarray(jax.device_get(steps), np.float64)
            loss, main, count, extra, extra_count = steps.T
            counted = float(count.sum())
            acc = add_weighted(acc, p, counted)
            del p
            total += counted
            rows_of["loss"].append((loss * count).sum() / max(counted, 1.0))
            rows_of["main"].append(main.sum() / max(counted, 1.0))
            rows_of["mtp"].append(extra.sum() / max(extra_count.sum(), 1.0))
        on_device = jax.device_put(g)
        new, m = server(on_device,
                        zeros(on_device) if m is None else jax.device_put(m),
                        acc, total)
        g, m = host(new), host(m) if r + 1 < rounds else None
        del new, on_device, acc
        for name in out:
            out[name].append(rows_of[name])
    return {name: np.asarray(v, np.float64) for name, v in out.items()}, g
