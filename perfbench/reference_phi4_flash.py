"""The plain reference of the Phi-4-mini-flash cell: the decoder-hybrid-decoder
stack (SambaY) and FedAvg with server momentum, in straight ``jax.numpy`` and
float32 at ``highest`` matmul precision, independent of ``fedtpu/``.

The stack (microsoft/Phi-4-mini-flash-reasoning, ``config.json``,
``model_type: phi4flash``; arXiv:2507.06607, with arXiv:2406.07522,
arXiv:2312.00752, arXiv:2405.05254 and arXiv:2410.05258 behind it). ``u`` is a
layer's normed input. Every layer is ``x <- x + Mixer(LN_1(x))``, ``x <- x +
FF(LN_2(x))``, LayerNorm with gain and bias; a final LayerNorm and ``logits =
h E^T``, ``E`` the embedding (tied, no bias). No positions anywhere. A
layer's kind follows from its PUBLISHED index ``i`` among ``num_hidden_layers``
(``kind_at``): below the half even layers are Mamba-1 mixers and odd ones
window attention; the layer at the half is the Mamba-1 mixer that keeps the
memory, the next full attention that keeps its keys and values, and after them
even layers are Gated Memory Units and odd ones cross-attention.

* **feed-forward**: ``[g | v] = u W_1``, ``(v * SiLU(g)) W_2``.
* **Mamba-1** (``s6``): ``[x | z] = u W_in``; ``x <- SiLU(conv(x) + b_c)``,
  depthwise, causal, over the last ``taps`` positions of the same document;
  ``[dr | B | C] = x W_x``; ``dl = softplus(dr W_d + b_d)``; ``A =
  -exp(A_log)``. **The recurrence runs token by token** (``s6_token``): ``h <-
  exp(dl_t (x) A) * h + (dl_t * x_t) (x) B_t``, ``h`` zero first at a
  document's first token; ``y_t = h C_t + D * x_t``. A ``lax.scan`` over tokens
  inside a ``lax.scan`` over blocks of ``TOKEN_BLOCK`` of them whose body is
  recomputed in the backward pass, so that 4,096 states of 328 KB do not live
  at once: memory only. ``(y * SiLU(z)) W_out``; the memory is ``y``.
* **Gated Memory Unit**: ``(m * SiLU(u W_1)) W_2``.
* **attention**: ``[q | k | v] = u W_qkv + b`` (cross: ``q = u W_q + b`` and
  the full layer's ``k``, ``v``), heads of ``d``; whole ``(T, T)`` scores a
  few pairs of heads at a time, scaled by ``d^-1/2``, the mask written out:
  same document, ``0 <= t - s``, and ``t - s < sliding_window`` in a window
  layer. Differential, term by term: heads ``2i``, ``2i + 1`` are a pair with
  ``q1, q2, k1, k2`` and the value ``V = [v_2i | v_2i+1]``; ``a = softmax(q1
  k1^T) V - l softmax(q2 k2^T) V``, ``l = exp(lq1 . lk1) - exp(lq2 . lk2) +
  l0``, ``l0 = 0.8 - 0.6 exp(-0.3 i)``; ``o = (1 - l0) w * RMSNorm(a)`` over
  the ``2d`` of a pair; ``o W_o + b_o``.

Departures from the published model, each an input or a statement of the
configuration and none of the mathematics: which of the published layers are
held (``layers_held``) is handed in; the weights are random; the vocabulary
is a slice (a smaller vocabulary); a row is a packed sequence whose segments
are documents: state, convolution and attention restart at a document's
first token, padding (segment 0) and each document's last token are out of
the loss. What the config leaves to the model's code is listed under
``assumed`` in the configuration's file.

FedAvg with server momentum as ``reference_lm.py`` writes it (its own copy).
"""

from __future__ import annotations

import concurrent.futures
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Pairs of heads whose whole (T, T) scores exist at one time.
PAIR_BLOCK = 2
# Tokens of the recurrence whose states are kept at one time in the backward
# pass (the blocks' first states besides).
TOKEN_BLOCK = 64
# Rows of the sequence whose whole logits exist at one time in the loss.
ROW_BLOCK = 1024
# How ``compiled_step`` asks for its pieces to be compiled: the compiler's
# least effort on the running time (``reference_kimi_linear`` has the
# numbers: the same operations, a tenth of the compile).
STEP_COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0}


def _mm(a, b):
    """Every large matrix product of the stack goes through here."""
    return a @ b


def _layer_norm(x, gain, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain + bias


def _starts(segs):
    """Where a document (or a stretch of padding) begins."""
    return jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])


def kind_at(index: int, cfg: dict) -> str:
    """The kind of the layer at the published ``index``."""
    half, state_space = (cfg["num_hidden_layers"] // 2,
                         index % cfg["mb_per_layer"] == 0)
    if index <= half:
        if not state_space:
            return "window"
        return "s6_memory" if index == half else "s6"
    if index == half + 1:
        return "full"
    return "gmu" if state_space else "cross"


def held(cfg: dict) -> tuple:
    return tuple(cfg["layers_held"]) or tuple(range(cfg["num_hidden_layers"]))


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


# ---------------------------------------------------------------- Mamba-1
def short_conv(x, w, bias, starts):
    """``y_t = bias + sum_j w[K - 1 - j] x_{t-j}`` over the ``j < K``
    positions back that lie in ``t``'s own document; ``x (T, C)``, ``w (K,
    C)``."""
    taps, doc = w.shape[0], jnp.cumsum(starts)
    out = x * w[taps - 1] + bias
    for back in range(1, taps):
        earlier = jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]])
        same = jnp.concatenate([jnp.zeros((back,), bool),
                                doc[back:] == doc[:-back]])
        out = out + jnp.where(same[:, None], earlier, 0.0) * w[taps - 1 - back]
    return out


def s6_token(state, token, a):
    """One token of the recurrence: ``state`` and ``a`` ``(N, D)`` (the
    channels last: whole lanes of the chip); ``token = (x, dl (D,), b, c
    (N,), start)``. Returns the new state and ``y (D,)`` without the skip.
    Plain products and sums: float32 by statement."""
    x, dl, b, c, start = token
    state = (jnp.exp(dl[None, :] * a) * jnp.where(start, 0.0, state)
             + (dl * x)[None, :] * b[:, None])
    return state, (state * c[:, None]).sum(axis=0)


def s6_recurrence(x, dl, a, b, c, starts):
    """``y (T, D)``, token by token from a zero state; ``a (D, N)``."""
    t, a = x.shape[0], a.T
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    cut = lambda arr: arr.reshape(t // block, block, *arr.shape[1:])

    @jax.checkpoint     # a block's states are recomputed in the backward
    def some(state, tokens):    # pass, not kept: memory only
        return jax.lax.scan(lambda s, tok: s6_token(s, tok, a), state, tokens)

    _, y = jax.lax.scan(some, jnp.zeros(a.shape, jnp.float32),
                        jax.tree.map(cut, (x, dl, b, c, starts)))
    return y.reshape(x.shape)


def s6(layer, u, segs, cfg):
    """``(mixer(u), y)``: ``y`` the scan's output with the skip, before the
    gate."""
    inner, n = layer["A_log"].shape
    rank = layer["dt_proj"].shape[0]
    starts = _starts(segs)
    x, z = jnp.split(_mm(u, layer["in_proj"]), [inner], axis=-1)
    x = jax.nn.silu(short_conv(x, layer["conv_w"], layer["conv_b"], starts))
    dr, b, c = jnp.split(_mm(x, layer["x_proj"]), [rank, rank + n], axis=-1)
    dl = jax.nn.softplus(_mm(dr, layer["dt_proj"]) + layer["dt_bias"])
    y = (s6_recurrence(x, dl, -jnp.exp(layer["A_log"]), b, c, starts)
         + layer["D"] * x)
    return _mm(y * jax.nn.silu(z), layer["out_proj"]), y


def gmu(layer, u, memory):
    return _mm(memory * jax.nn.silu(_mm(u, layer["in_proj"])),
               layer["out_proj"])


# -------------------------------------------------------------- attention
def attention(layer, u, segs, cfg, l0, window, shared):
    """``(mixer(u), keys, values)``; ``l0`` the layer's ``lambda_init``,
    ``window`` its window or None; a layer with ``q`` reads the full layer's
    keys and values out of ``shared``."""
    t = u.shape[0]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = layer["o"].shape[0] // heads
    if "q" in layer:
        q = _mm(u, layer["q"]) + layer["q_bias"]
        keys, values = shared["keys"], shared["values"]
    else:
        q, keys, values = jnp.split(
            _mm(u, layer["qkv"]) + layer["qkv_bias"],
            [heads * d, (heads + kv) * d], axis=-1)
    # (pairs, 2, T, d): the pair's two queries, its two keys; (pairs, T, 2d):
    # its one value, the two heads' side by side
    q = q.reshape(t, heads // 2, 2, d).transpose(1, 2, 0, 3)
    k = keys.reshape(t, kv // 2, 2, d).transpose(1, 2, 0, 3)
    v = values.reshape(t, kv // 2, 2 * d).transpose(1, 0, 2)
    # query pair i reads key-value pair i // (heads / kv)
    k, v = (jnp.repeat(a, heads // kv, axis=0) for a in (k, v))
    idx = jnp.arange(t)
    allowed = ((idx[:, None] >= idx[None, :])
               & (segs[:, None] == segs[None, :]))
    if window is not None:
        allowed = allowed & (idx[:, None] - idx[None, :] < window)
    lam = (jnp.exp(layer["lambda_q1"] @ layer["lambda_k1"])
           - jnp.exp(layer["lambda_q2"] @ layer["lambda_k2"]) + l0)

    def pair(qkv):
        q_p, k_p, v_p = qkv                  # (2, T, d), (2, T, d), (T, 2d)
        scores = jnp.stack([_mm(q_p[i], k_p[i].T) for i in (0, 1)]) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
        first, second = _mm(probs[0], v_p), _mm(probs[1], v_p)
        a = first - lam * second
        normed = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True)
                                   + cfg["layer_norm_eps"])
        return (1.0 - l0) * layer["sub_norm"] * normed

    block = PAIR_BLOCK if (heads // 2) % PAIR_BLOCK == 0 else 1
    cut = lambda a: a.reshape(-1, block, *a.shape[1:])
    # a block's scores are recomputed in the backward pass, not kept for
    # every block at once: memory only
    out = jax.lax.map(jax.checkpoint(jax.vmap(pair)),
                      (cut(q), cut(k), cut(v)))
    out = out.reshape(heads // 2, t, 2 * d).transpose(1, 0, 2)
    return (_mm(out.reshape(t, heads * d), layer["o"]) + layer["o_bias"],
            keys, values)


# -------------------------------------------------------------- the model
def feed_forward(part, h, cfg):
    u = _layer_norm(h, part["norm"], part["norm_bias"], cfg["layer_norm_eps"])
    gate, up = jnp.split(_mm(u, part["gate_up"]), 2, axis=-1)
    return h + _mm(up * jax.nn.silu(gate), part["down"])


def block(kind, layer, h, shared, segs, cfg, l0):
    """One layer of ``kind`` on ``h (T, C)``: its mixer, then its
    feed-forward. ``shared`` holds ``memory``, ``keys`` and ``values`` (zeros
    until the layer that makes each): ``(h, shared)``."""
    part = layer["mixer"]
    u = _layer_norm(h, part["norm"], part["norm_bias"], cfg["layer_norm_eps"])
    if kind in ("s6", "s6_memory"):
        out, y = s6(part, u, segs, cfg)
        if kind == "s6_memory":
            shared = {**shared, "memory": y}
    elif kind == "gmu":
        out = gmu(part, u, shared["memory"])
    else:
        out, keys, values = attention(
            part, u, segs, cfg, l0,
            cfg["sliding_window"] if kind == "window" else None, shared)
        if kind == "full":
            shared = {**shared, "keys": keys, "values": values}
    return feed_forward(layer["ffn"], h + out, cfg), shared


def shared_zeros(params, t: int, cfg: dict) -> dict:
    """``memory``, ``keys`` and ``values`` before any layer has made them."""
    h = params["embed"].shape[1]
    kv = h // cfg["num_attention_heads"] * cfg["num_key_value_heads"]
    inner = next((layer["mixer"]["A_log"].shape[0]
                  for layer in params["layers"] if "A_log" in layer["mixer"]),
                 h)
    return {"memory": jnp.zeros((t, inner), jnp.float32),
            "keys": jnp.zeros((t, kv), jnp.float32),
            "values": jnp.zeros((t, kv), jnp.float32)}


def hidden(params, row, cfg):
    """The last layer's output ``(T, C)``, before the final norm."""
    # a layer's intermediates are recomputed in the backward pass, not kept
    # for the whole depth: memory only
    h = params["embed"][row[0]]
    shared = shared_zeros(params, row.shape[-1], cfg)
    for index, layer in zip(held(cfg), params["layers"]):
        run = jax.checkpoint(functools.partial(
            block, kind_at(index, cfg), segs=row[1], cfg=cfg,
            l0=lambda_init(index)))
        h, shared = run(layer, h, shared)
    return h


def logits(params, row, cfg):
    """``(T, vocab)``: the final norm and the tied head, whole."""
    return _mm(_layer_norm(hidden(params, row, cfg), params["final_norm"],
                           params["final_norm_bias"], cfg["layer_norm_eps"]),
               params["embed"].T)


def exits(final_norm, final_bias, embed, h, row, cfg):
    """``(mean loss, (summed loss, count))`` from the last layer's ``h``: a
    final norm, the tied head, whole logits over the vocabulary a block of
    ``ROW_BLOCK`` rows at a time (recomputed in the backward pass: memory
    only)."""
    tokens, segs = row[0], row[1]
    ahead = lambda a: jnp.concatenate([a[1:], jnp.zeros((1,), a.dtype)])
    valid = ((segs > 0) & (ahead(segs) == segs)).astype(jnp.float32)
    x = _layer_norm(h, final_norm, final_bias, cfg["layer_norm_eps"])
    t = x.shape[0]
    block = ROW_BLOCK if t % ROW_BLOCK == 0 else t
    cut = lambda a: a.reshape(t // block, block, *a.shape[1:])

    def some(rows):
        x_b, labels, valid_b = rows
        logp = jax.nn.log_softmax(_mm(x_b, embed.T), axis=-1)
        ll = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        return -(ll * valid_b).sum()

    total = jax.lax.map(jax.checkpoint(some),
                        (cut(x), cut(ahead(tokens)), cut(valid))).sum()
    return total / jnp.maximum(valid.sum(), 1.0), (total, valid.sum())


def mean_loss(params, row, cfg):
    """The mean next-token loss of one packed row ``(2, T)`` (tokens and
    segment ids), and its two sums: the whole model as one function."""
    return exits(params["final_norm"], params["final_norm_bias"],
                 params["embed"], hidden(params, row, cfg), row, cfg)


def sequence_loss(params, row, cfg):
    """``(summed loss, tokens counted)``."""
    return mean_loss(params, row, cfg)[1]


def compiled_step(params, row, cfg: dict, learning_rate: float):
    """One SGD step on ``mean_loss`` of one packed row, compiled from shapes
    alone (``params`` and ``row`` may be ``ShapeDtypeStruct``s): ``step(p,
    row) -> (p - lr grad, loss, (summed loss, count))``; ``p`` is used up.

    The same step as ``jax.grad(mean_loss)`` (a self-test holds them equal),
    run a LAYER AT A TIME: the forward pass keeps each layer's inputs (``h``
    and the three shared arrays); the backward pass walks the layers in
    reverse, each one's ``jax.vjp`` giving its leaves' gradient, applied
    there, and the cotangents of ``h`` and of the shared arrays, which so
    come summed from every layer that read them. The layers of a kind are
    one compiled function (``l0`` is an argument). The embedding takes its
    two gradients, the head's and the rows', in one update."""
    frozen = dict(cfg)
    where = getattr(row, "sharding", None)      # a described device's, or none
    spec = lambda a, dtype=jnp.float32: jax.ShapeDtypeStruct(
        a.shape, dtype, sharding=where)
    shapes = jax.tree.map(spec, params)
    t = row.shape[-1]
    tokens = segs = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=where)
    rows = jax.ShapeDtypeStruct(row.shape, jnp.int32, sharding=where)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=where)
    sgd = lambda leaves, grads: jax.tree.map(
        lambda a, b: a - learning_rate * b, leaves, grads)
    order = [(index, kind_at(index, frozen)) for index in held(frozen)]

    def forward(kind, layer, h, shared, segs, l0):
        return block(kind, layer, h, shared, segs, frozen, l0)

    def backward(kind, layer, h, shared, segs, l0, g_h, g_shared):
        _, pull = jax.vjp(
            lambda l, a, s: block(kind, l, a, s, segs, frozen, l0),
            layer, h, shared)
        g_layer, g_h, g_shared = pull((g_h, g_shared))
        return sgd(layer, g_layer), g_h, g_shared

    def exits_back(final_norm, final_bias, embed, h, row):
        (loss, sums), grads = jax.value_and_grad(
            lambda *a: exits(*a, row, frozen), argnums=(0, 1, 2, 3),
            has_aux=True)(final_norm, final_bias, embed, h)
        return loss, sums, grads

    def enter_back(embed, g_head, tokens, g):
        _, pull = jax.vjp(lambda e: e[tokens], embed)
        return sgd(embed, jax.tree.map(jnp.add, g_head, pull(g)[0]))

    h_spec = jax.ShapeDtypeStruct((t, params["embed"].shape[1]), jnp.float32,
                                  sharding=where)
    shared_spec = jax.tree.map(spec, jax.eval_shape(
        lambda: shared_zeros(shapes, t, frozen)))
    kinds = {}
    for (_, kind), layer in zip(order, shapes["layers"]):
        kinds.setdefault(kind, layer)

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
        back = {k: compile_(functools.partial(backward, k), v, h_spec,
                            shared_spec, segs, scalar, h_spec, shared_spec,
                            donate=(0, 5, 6)) for k, v in kinds.items()}
        fore = {k: compile_(functools.partial(forward, k), v, h_spec,
                            shared_spec, segs, scalar)
                for k, v in kinds.items()}
        go_out = compile_(exits_back, shapes["final_norm"],
                          shapes["final_norm_bias"], shapes["embed"], h_spec,
                          rows)
        go_in = compile_(lambda e, tok: e[tok], shapes["embed"], tokens)
        come_back = compile_(enter_back, shapes["embed"], shapes["embed"],
                             tokens, h_spec, donate=(0,))
        zeros = compile_(lambda: shared_zeros(shapes, t, frozen))
        back = {k: v.result() for k, v in back.items()}
        fore = {k: v.result() for k, v in fore.items()}
        go_out, go_in, come_back, zeros = (go_out.result(), go_in.result(),
                                           come_back.result(), zeros.result())
    apply = jax.jit(sgd, donate_argnums=(0,))

    def step(p, row):
        tokens, segs = row[0], row[1]
        h, shared, kept = go_in(p["embed"], tokens), zeros(), []
        for (index, kind), layer in zip(order, p["layers"]):
            kept.append((h, shared))
            h, shared = fore[kind](layer, h, shared, segs,
                                   jnp.float32(lambda_init(index)))
        loss, sums, (g_final, g_bias, g_head, g_h) = go_out(
            p["final_norm"], p["final_norm_bias"], p["embed"], h, row)
        del h, shared
        g_shared, new = zeros(), []
        for (index, kind), layer in zip(reversed(order),
                                        reversed(p["layers"])):
            layer, g_h, g_shared = back[kind](
                layer, *kept.pop(), segs, jnp.float32(lambda_init(index)),
                g_h, g_shared)
            new.append(layer)
        new.reverse()
        return ({"embed": come_back(p["embed"], g_head, tokens, g_h),
                 "layers": tuple(new),
                 "final_norm": apply(p["final_norm"], g_final),
                 "final_norm_bias": apply(p["final_norm_bias"], g_bias)},
                loss, sums)

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
