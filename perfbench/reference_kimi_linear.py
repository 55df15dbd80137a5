"""The plain reference of the Kimi-Linear cell: the stack and FedAvg with
server momentum, in straight ``jax.numpy`` and float32 at ``highest`` matmul
precision, independent of ``fedtpu/``.

The stack (moonshotai/Kimi-Linear-48B-A3B-Instruct, ``config.json``,
``model_type: kimi_linear``; the layer code is ``fla.layers.kda.
KimiDeltaAttention`` and ``modeling_kimi.py``'s ``KimiMLAAttention`` /
``KimiSparseMoeBlock``): every layer is ``h + Mixer(RMSNorm(h))``, then ``h +
FFN(RMSNorm(h))``; a final RMSNorm and an untied head.

* **KDA** (a mixer that has ``q_conv``): ``q, k, v = SiLU(conv(x W))``, the
  convolution depthwise and causal over the last ``short_conv_kernel_size``
  positions of the same document, no bias; a head's ``q <- q / sqrt(|q|^2 +
  1e-6) * d^-1/2``, ``k <- k / sqrt(|k|^2 + 1e-6)``; ``g = -exp(A_log)
  softplus(x W_f1 W_f2 + dt_bias)`` a head and key channel, ``beta =
  sigmoid(x W_b)`` a head. **The recurrence runs token by token**
  (``fla.ops.kda.naive.naive_recurrent_kda``; ``kda_token`` is one token):
  ``S <- Diag(exp g_t) S``, zero first at a document's first token; ``S <- S +
  beta_t k_t (v_t - S^T k_t)^T``; ``o_t = S^T q_t``. A ``lax.scan`` over
  tokens inside a ``lax.scan`` over blocks of ``TOKEN_BLOCK`` of them whose
  body is recomputed in the backward pass, so that 4,096 states of 2 MB do
  not live at once. Then ``W_o [w * RMSNorm_head(o) * sigmoid(x W_g1 W_g2 +
  b_g)]``.
* **latent attention** (a mixer that has ``kv_a``): ``q = x W_q`` in heads of
  ``nope | rope`` columns, NO bottleneck; ``[c_kv | k_r] = x W_kva``, ``[k_n |
  v] = RMSNorm(c_kv) W_kvb``; a head's key is ``[k_n | k_r]``, ``k_r`` one
  vector for all heads; NOTHING is rotated (``mla_use_nope``);
  ``softmax(q k^T (nope + rope)^-1/2)``, causal within a document, whole
  ``(T, T)`` scores a few heads at a time.
* **feed-forward**: a layer without a router is ``W_down(silu(W_gate x) *
  W_up x)``; one with a router scores ``s = sigmoid(x W_r)`` over all experts,
  chooses the top ``num_experts_per_token`` of ``s + bias``, weighs them
  ``routed_scaling_factor * s / (sum s + 1e-20)`` (``moe_renormalize``) and
  adds one shared expert; the held experts are computed DENSELY, every one on
  every token, weighted by the gate and zero elsewhere.

Departures from the published model, each an input or a statement of the
configuration and none of the mathematics: the depth and which layer is of
which kind are the parameters handed in; the weights are random; **the
share**: the parameters hold experts ``[first_expert, first_expert + held)``
of every expert layer and a slice of the vocabulary, the router still scores
all its experts, and what the absent experts would have added is left out; no
auxiliary or balancing loss and the selection bias is never updated; a row is
a packed sequence whose segments are documents: state, convolution and
attention restart at a document's first token, padding (segment 0) and each
document's last token are out of the loss. What the config leaves to the code
is listed under ``assumed`` in the configuration's file.

FedAvg with server momentum as ``reference_lm.py`` writes it (its own copy).
"""

from __future__ import annotations

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
import numpy as np

# Heads whose whole (T, T) scores exist at one time.
HEAD_BLOCK = 4
# Tokens of the recurrence whose states are kept at one time in the backward
# pass (the blocks' first states besides).
TOKEN_BLOCK = 64
L2_EPS = 1e-6
# How ``compiled_step`` asks for its pieces to be compiled: the compiler's
# least effort on the running time. One float32 matmul of 4,096 x 2,304 x
# 4,096 at 'highest' precision with its two gradients compiles in 9.4 s for a
# v5e at the default effort and in 0.9 s so (the same operations, less
# unrolling), and a run of the cell pays this compile before its first round
# beside the round program's; the step runs sixteen times.
STEP_COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0}


def _mm(a, b):
    """Every large matrix product of the stack goes through here."""
    return a @ b


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _starts(segs):
    """Where a document (or a stretch of padding) begins."""
    return jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])


# -------------------------------------------------------------------- KDA
def short_conv(x, w, starts):
    """``y_t = sum_j w[K - 1 - j] x_{t-j}`` over the ``j < K`` positions back
    that lie in ``t``'s own document; ``x (T, C)``, ``w (K, C)``."""
    taps, doc = w.shape[0], jnp.cumsum(starts)
    out = x * w[taps - 1]
    for back in range(1, taps):
        earlier = jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
        same = jnp.concatenate([jnp.zeros((back,), bool),
                                doc[back:] == doc[:-back]])
        out = out + jnp.where(same[:, None], earlier, 0.0) * w[taps - 1 - back]
    return out


def kda_token(state, token):
    """One token of the recurrence, every head: ``state (heads, d_k, d_v)``;
    ``token = (q, k, v (heads, d), g (heads, d_k), beta (heads,), start)``.
    Returns the new state and ``o (heads, d_v)``. Plain products and sums:
    float32 by statement, whatever the matmul precision."""
    q, k, v, g, beta, start = token
    state = jnp.where(start, 0.0, state) * jnp.exp(g)[:, :, None]
    u = beta[:, None] * (v - (state * k[:, :, None]).sum(axis=1))
    state = state + k[:, :, None] * u[:, None, :]
    return state, (state * q[:, :, None]).sum(axis=1)


def kda_recurrence(q, k, v, g, beta, starts):
    """``o (T, heads, d_v)``, token by token from a zero state."""
    t, heads, dk = k.shape
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    cut = lambda a: a.reshape(t // block, block, *a.shape[1:])

    @jax.checkpoint     # a block's states are recomputed in the backward
    def some(state, tokens):    # pass, not kept: memory only
        return jax.lax.scan(lambda s, x: kda_token(s, x), state, tokens)

    _, o = jax.lax.scan(some, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32),
                        jax.tree.map(cut, (q, k, v, g, beta, starts)))
    return o.reshape(t, heads, -1)


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
    beta = jax.nn.sigmoid(_mm(x, layer["b_proj"]))
    o = kda_recurrence(q, k, v, g, beta, starts)
    gate = (_mm(_mm(x, layer["g_a"]), layer["g_b"]) + layer["g_bias"]).reshape(
        t, heads, d)
    y = _rms(o, layer["o_norm"], cfg["rms_norm_eps"]) * jax.nn.sigmoid(gate)
    return _mm(y.reshape(t, heads * d), layer["o_proj"])


# ------------------------------------------------------- latent attention
def attention(layer, x, segs, cfg):
    """Latent attention without positions on the normed input ``x (T, C)``."""
    t, heads = x.shape[0], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    q = _mm(x, layer["q"]).reshape(t, heads, nope + rope)
    kv = _mm(x, layer["kv_a"])
    k_r = kv[:, rank:]                                              # (T, rope)
    kv = _mm(_rms(kv[:, :rank], layer["kv_a_norm"], cfg["rms_norm_eps"]),
             layer["kv_b"]).reshape(t, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r[:, None], (t, heads, rope))],
                        axis=-1)
    v = kv[..., nope:]
    idx = jnp.arange(t)
    allowed = (idx[:, None] >= idx[None]) & (segs[:, None] == segs[None])
    scale = (nope + rope) ** -0.5

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


def gate_weights(x, router, bias, top_k: int, renormalize: bool, scale: float):
    """``(T, E)``: an expert's weight where it is among the token's chosen,
    zero elsewhere."""
    scores = jax.nn.sigmoid(x @ router)             # float32 by statement
    choice = scores + bias
    kth = jnp.sort(choice, axis=-1)[:, -top_k][:, None]
    w = jnp.where(choice >= kth, scores, 0.0)
    if renormalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * scale


def experts(layer, x, cfg):
    """The held experts' part of the routed sum, densely, and the shared
    expert; ``layer["up"]`` holds experts ``[first_expert, first_expert +
    held)`` of the ``router``'s."""
    w = gate_weights(x, layer["router"], layer["router_bias"],
                     cfg["num_experts_per_token"], cfg["moe_renormalize"],
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
def kind_of(part) -> str:
    """What its leaves make a sublayer: a mixer is ``"kda"`` or ``"full"``,
    a feed-forward ``"experts"`` or ``"dense"``."""
    for leaf, kind in (("q_conv", "kda"), ("kv_a", "full"),
                       ("router", "experts")):
        if leaf in part:
            return kind
    return "dense"


def sublayer(part, h, segs, cfg):
    """``h + F(RMSNorm(h))`` for the mixer or the feed-forward ``part``."""
    x = _rms(h, part["norm"], cfg["rms_norm_eps"])
    kind = kind_of(part)
    if kind == "kda":
        return h + kda(part, x, segs, cfg)
    if kind == "full":
        return h + attention(part, x, segs, cfg)
    if kind == "experts":
        return h + experts(part, x, cfg)
    return h + gated(x, part["gate"], part["up"], part["down"])


def block(layer, h, segs, cfg):
    """One layer on ``h (T, C)``: its mixer, then its feed-forward."""
    return sublayer(layer["ffn"], sublayer(layer["mixer"], h, segs, cfg), segs,
                    cfg)


def exits(final_norm, head, h, row, cfg):
    """``(mean loss, (summed loss, count))`` from the last layer's ``h``: a
    final norm, the head, whole ``(T, vocab)`` logits."""
    tokens, segs = row[0], row[1]
    ahead = lambda a: jnp.concatenate([a[1:], jnp.zeros((1,), a.dtype)])
    valid = ((segs > 0) & (ahead(segs) == segs)).astype(jnp.float32)
    logp = jax.nn.log_softmax(
        _mm(_rms(h, final_norm, cfg["rms_norm_eps"]), head), axis=-1)
    ll = jnp.take_along_axis(logp, ahead(tokens)[:, None], axis=1)[:, 0]
    total = -(ll * valid).sum()
    return total / jnp.maximum(valid.sum(), 1.0), (total, valid.sum())


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


def sequence_loss(params, row, cfg):
    """``(summed loss, tokens counted)``."""
    return mean_loss(params, row, cfg)[1]


def compiled_step(params, row, cfg: dict, learning_rate: float):
    """One SGD step on ``mean_loss`` of one packed row, compiled from shapes
    alone (``params`` and ``row`` may be ``ShapeDtypeStruct``s): ``step(p,
    row) -> (p - lr grad, loss, (summed loss, count))``; ``p`` is used up.

    The same step as ``jax.grad(mean_loss)`` (a self-test holds them equal),
    run a SUBLAYER AT A TIME: the forward pass keeps each sublayer's input;
    the backward pass walks them in reverse, each one's ``jax.vjp`` giving
    its leaves' gradient, applied there, and its input's cotangent. The
    sublayers of a kind (``kind_of``: four of them) are one compiled
    function, so the four KDA mixers and the four expert feed-forwards
    compile once each (``STEP_COMPILER_OPTIONS``).
    Nothing else differs: the pieces are the embedding's gather,
    ``sublayer`` and ``exits``, what ``mean_loss`` is made of."""
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
    function of no argument that makes them on the device) over
    ``client_rows`` (a list, one ``(n_c, 2, T)`` int32 array a client).
    Returns ``(losses (rounds, C), global parameters after the last round,
    on the host)``. Every client in turn starts from the global model and
    runs one epoch of one-sequence SGD steps; the server takes the mean of
    the clients' deltas weighted by the tokens each counted in its loss and
    applies it with momentum (``m = beta m + delta``, ``g += lr m``). A
    client's loss of a round is the mean of its steps' losses weighted by
    those tokens, each at the parameters the step started from.

    The device holds one client's copy, the round's weighted sum of the
    clients' parameters and (inside a step) a layer's gradient; the global
    model and the server's momentum wait on the HOST while the clients
    train, so that the run's peak of memory stays the round program's, not
    this reference's. The mean delta is therefore ``sum(w p) / W - global``,
    not ``sum(w (p - global)) / W``: the same number to a few units in the
    last place of a parameter, which is where the sum ``global + step``
    rounds anyway. ``step`` is ``compiled_step``'s, compiled here from
    shapes alone where none is handed in."""
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
    m, out = None, []
    for r in range(rounds):
        acc, losses, total = None, [], 0.0
        for rows in client_rows:
            p, steps = jax.device_put(g), []
            acc = zeros(p) if acc is None else acc
            for row in rows:
                p, loss, sums = step(p, jnp.asarray(row, jnp.int32))
                steps.append((loss, sums[1]))
            loss, count = np.asarray(jax.device_get(steps), np.float64).T
            counted = float(count.sum())
            acc = add_weighted(acc, p, counted)
            del p
            total += counted
            losses.append((loss * count).sum() / max(counted, 1.0))
        on_device = jax.device_put(g)
        new, m = server(on_device,
                        zeros(on_device) if m is None else jax.device_put(m),
                        acc, total)
        g, m = host(new), host(m) if r + 1 < rounds else None
        del new, on_device, acc
        out.append(losses)
    return np.asarray(out, np.float64), g
