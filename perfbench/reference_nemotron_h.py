"""The plain reference of the hybrid cells: the ``nemotron_h`` tower and FedAvg
with server momentum, in straight ``jax.numpy`` and float32 at ``highest``
matmul precision, independent of ``fedtpu/``.

The tower (nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, ``config.json``,
``model_type: nemotron_h``): every layer is one mixer behind one pre-norm,
``h <- h + mixer(RMSNorm(h; w, eps))``, chosen by the letter of
``hybrid_override_pattern``; after the last a final RMSNorm and an untied
linear head; the whole ``[T, vocab]`` logits at once.

* ``M``, Mamba-2, as ``transformers``' ``Mamba2Mixer.torch_forward`` computes
  it: ``[z | xBC | dt] = x W_in``; ``xBC <- silu(conv(xBC) + b)``, a causal
  depthwise convolution over the last ``conv_kernel`` positions; ``x``, ``B``,
  ``C`` split from it (head ``h`` reads group ``h // (heads / n_groups)``);
  ``dt <- softplus(dt + dt_bias)`` with no clamp (``time_step_limit`` (0,
  inf)); ``A = -exp(A_log)``; the state of a head, ``head_dim x state``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``,
  computed HERE token by token (a ``lax.scan`` over positions; no chunks);
  then ``w * RMSNorm(y * silu(z))`` over each of ``n_groups`` equal groups of
  the inner width (gate first; ``Zamba2RMSNormGated`` is the grouped form)
  and ``W_out``.
* ``*``, attention: ``num_attention_heads`` query heads of ``head_dim`` over
  ``num_key_value_heads`` key-value heads (query head ``i`` reads key-value
  head ``i // (heads / kv)``), ``softmax(q k^T / sqrt(head_dim))``, causal;
  no positional encoding.
* ``E``, sparse experts beside a shared one, the router as ``transformers``'
  ``DeepseekV3TopkRouter``: ``s = sigmoid(x W_r)`` in float32 over all
  experts; chosen = top ``num_experts_per_tok`` of ``s + bias``; ``w =
  s[chosen]``, ``w / (sum w + 1e-20)`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; an expert is ``W_down relu(W_up x)^2``; computed
  DENSELY here, every held expert on every token, weighted by the gate and
  zero elsewhere (all held experts in two products); the shared expert on
  every token.

Departures from the published model, each an input or a statement of the
configuration and none of the mathematics: the depth and the order of kinds
are the pattern handed in; the weights are random; **the share**: the
parameters hold experts ``[first_expert, first_expert + held)`` of every
expert layer and a slice of the vocabulary, the router still scores all its
experts, and what the absent experts would have added is left out (the cut
the model-configs guide sets out); no auxiliary or balancing loss and the
selection bias is never updated; a row is a packed sequence whose segments
are documents: attention stays within a document, the state-space layer's
state is zero at a document's first token and its convolution reads zeros
before it, and padding (segment 0) and each document's last token are out
of the loss. The release's second, denoising tower is in no public
``config.json`` and is not here.

FedAvg with server momentum as ``reference_lm.py`` writes it (its own copy).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
# Positions whose per-token states the backward pass holds at one time: the
# recurrence is recomputed block by block (memory only, no arithmetic).
SCAN_BLOCK = 128


def _mm(a, b):
    """Every large matrix product of the tower goes through here."""
    return a @ b


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _starts(segs):
    return jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])


# ---------------------------------------------------------------- mamba-2
def conv(x, w, b, starts):
    """``out_t = b + sum_j w[j] x_{t - (K-1) + j}`` over the positions of
    ``t``'s own document (zeros before its first token)."""
    taps, t = w.shape[0], x.shape[0]
    doc = jnp.cumsum(starts.astype(jnp.int32))
    out = b + jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        earlier = jnp.pad(x, ((back, 0), (0, 0)))[:t]
        same = jnp.pad(doc, (back, 0))[:t] == doc
        out = out + jnp.where(same[:, None], earlier * w[j], 0.0)
    return out


def recurrence(x, dt, a, b, c, starts):
    """``y (T, heads, P)``, token by token. ``x (T, heads, P)``, ``dt (T,
    heads)``, ``a (heads,)``, ``b``, ``c`` ``(T, heads, N)`` (each head's
    group's), ``starts (T,)``: the state is zero before a document's first
    token, so the decay into one is zero."""
    t, heads, p = x.shape
    n = b.shape[-1]
    decay = jnp.where(starts[:, None], 0.0, jnp.exp(dt * a))        # (T, heads)
    dtx = dt[..., None] * x

    def step(state, inputs):
        dtxt, decayt, bt, ct = inputs
        state = state * decayt[:, None, None] + dtxt[:, :, None] * bt[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, ct)

    block = SCAN_BLOCK if t % SCAN_BLOCK == 0 else t

    @jax.checkpoint
    def run_block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    blocks = jax.tree.map(lambda arr: arr.reshape(-1, block, *arr.shape[1:]),
                          (dtx, decay, b, c))
    _, y = jax.lax.scan(run_block, jnp.zeros((heads, p, n), jnp.float32),
                        blocks)
    return y.reshape(t, heads, p)


def gated_norm(y, z, gain, groups, eps):
    y = y * jax.nn.silu(z)
    parts = y.reshape(y.shape[0], groups, -1)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(y.shape) * gain


def mamba_mixer(layer, x, segs, cfg):
    """The mixer on the normed input ``x (T, hidden)``."""
    t = x.shape[0]
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    width, state = heads * p, groups * n
    starts = _starts(segs)
    proj = _mm(x, layer["in_proj"])
    z, xbc, dt = (proj[:, :width], proj[:, width:2 * width + 2 * state],
                  proj[:, 2 * width + 2 * state:])
    xbc = jax.nn.silu(conv(xbc, layer["conv_w"], layer["conv_b"], starts))
    xs = xbc[:, :width].reshape(t, heads, p)
    per_head = lambda arr: jnp.repeat(arr.reshape(t, groups, n),
                                      heads // groups, axis=1)
    b, c = per_head(xbc[:, width:width + state]), per_head(xbc[:, width + state:])
    dt = jax.nn.softplus(dt + layer["dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(layer["A_log"]), b, c, starts)
    y = (y + layer["D"][:, None] * xs).reshape(t, width)
    return _mm(gated_norm(y, z, layer["gate_norm"], groups,
                          cfg["layer_norm_epsilon"]), layer["out_proj"])


# -------------------------------------------------------------- attention
def attention_mixer(layer, x, segs, cfg):
    t = x.shape[0]
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    q = _mm(x, layer["q"]).reshape(t, heads, hd).transpose(1, 0, 2)
    shared = lambda w: jnp.repeat(_mm(x, w).reshape(t, kv, hd).transpose(1, 0, 2),
                                  heads // kv, axis=0)
    k, v = shared(layer["k"]), shared(layer["v"])
    idx = jnp.arange(t)
    allowed = (idx[:, None] >= idx[None]) & (segs[:, None] == segs[None])

    @jax.checkpoint     # a head's (T, T) scores are recomputed in the
    def head(qkv):       # backward pass, not kept for all heads: memory only
        qh, kh, vh = qkv
        scores = _mm(qh, kh.T) / np.sqrt(hd)
        return _mm(jax.nn.softmax(jnp.where(allowed, scores, -1e30), axis=-1),
                   vh)

    # a loop over the heads, each its whole (T, T) softmax
    ctx = jax.lax.map(head, (q, k, v)).transpose(1, 0, 2).reshape(t, heads * hd)
    return _mm(ctx, layer["o"])


# ---------------------------------------------------------------- experts
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


def expert(x, up, down):
    return _mm(jnp.square(jax.nn.relu(_mm(x, up))), down)


def experts_mixer(layer, x, cfg):
    """The held experts' part of the routed sum, densely, and the shared
    expert. ``layer["up"]`` holds experts ``[first_expert, first_expert +
    held)`` of the ``router``'s. Every held expert on every token in two
    products: the up-projections side by side, each expert's activations
    times its gate (zero where the token did not choose it), the
    down-projections one above the other, which sums over the experts."""
    w = gate_weights(x, layer["router"], layer["router_bias"],
                     cfg["num_experts_per_tok"], cfg["norm_topk_prob"],
                     cfg["routed_scaling_factor"])
    first, (held, hidden, width) = cfg.get("first_expert", 0), layer["up"].shape
    up = layer["up"].transpose(1, 0, 2).reshape(hidden, held * width)
    act = jnp.square(jax.nn.relu(_mm(x, up)))
    act = act * jnp.repeat(w[:, first:first + held], width, axis=1)
    routed = _mm(act, layer["down"].reshape(held * width, hidden))
    return routed + expert(x, layer["shared_up"], layer["shared_down"])


# -------------------------------------------------------------- the model
def layers_of(params, cfg):
    """``(kind, layer parameters)`` in the pattern's order; a kind's layers
    are a tuple under its name."""
    seen = dict.fromkeys(KINDS.values(), 0)
    for letter in cfg["hybrid_override_pattern"]:
        kind = KINDS[letter]
        yield kind, params[kind][seen[kind]]
        seen[kind] += 1


def sequence_loss(params, row, cfg):
    """``(summed next-token loss, tokens counted)`` of one packed row
    ``(2, T)``: tokens and segment ids."""
    tokens, segs = row[0], row[1]
    eps = cfg["layer_norm_epsilon"]
    h = params["embed"][tokens]

    def mixer(kind, layer, h):
        x = _rms(h, layer["norm"], eps)
        if kind == "mamba":
            return h + mamba_mixer(layer, x, segs, cfg)
        if kind == "attention":
            return h + attention_mixer(layer, x, segs, cfg)
        return h + experts_mixer(layer, x, cfg)

    for kind, layer in layers_of(params, cfg):
        # a layer's intermediates are recomputed in the backward pass, not
        # kept for the whole depth: memory only
        h = jax.checkpoint(functools.partial(mixer, kind))(layer, h)
    logits = _mm(_rms(h, params["final_norm"], eps), params["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    nxt = jnp.concatenate([segs[1:], jnp.zeros((1,), segs.dtype)])
    valid = ((segs > 0) & (nxt == segs)).astype(jnp.float32)
    ll = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -(ll * valid).sum(), valid.sum()


def mean_loss(params, row, cfg):
    loss, count = sequence_loss(params, row, cfg)
    return loss / jnp.maximum(count, 1.0), (loss, count)


def fedavgm_rounds(init_params, client_rows, rounds: int, cfg: dict,
                   learning_rate: float, momentum: float = 0.9,
                   server_lr: float = 1.0):
    """``rounds`` rounds from the global ``init_params`` (arrays, or a
    function of no argument that makes them on the device) over
    ``client_rows`` (a list, one ``(n_c, 2, T)`` int32 array a client).
    Returns ``(losses (rounds, C), global parameters after the last round,
    on the host)``. Every client in turn starts from the global model and
    runs one epoch of one-sequence SGD steps; the server takes the mean of
    the clients' deltas weighted by the tokens each counted in its loss and
    applies it with momentum (``m = beta m + delta``, ``g += lr m``). A
    client's loss of a round is the token-weighted mean of its steps'
    losses, each at the parameters the step started from.

    The device holds the global model, the round's sum of weighted deltas,
    one client's copy and (inside a step) its gradient; the server's
    momentum waits on the host while the clients train (it is zero before
    the first round and is dropped after the last). The step is compiled
    first, from shapes alone."""
    frozen = dict(cfg)
    make = init_params if callable(init_params) else lambda: init_params

    def sgd_step(p, row):
        (_, (loss, count)), g = jax.value_and_grad(
            lambda q: mean_loss(q, row, frozen), has_aux=True)(p)
        return jax.tree.map(lambda a, b: a - learning_rate * b, p, g), loss, count

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def add_delta(acc, p, g, w):
        return jax.tree.map(lambda a, b, c: a + w * (b - c), acc, p, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def server(g, m, acc, total):
        m = jax.tree.map(lambda a, b: momentum * a + b / total, m, acc)
        return jax.tree.map(lambda a, b: a + server_lr * b, g, m), m

    copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    with jax.default_matmul_precision("highest"):
        sgd_step = jax.jit(sgd_step, donate_argnums=(0,)).lower(
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32),
                         jax.eval_shape(make)),
            jax.ShapeDtypeStruct(client_rows[0].shape[1:], jnp.int32)).compile()
    g = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), make())
    m, losses = None, []
    with jax.default_matmul_precision("highest"):
        for r in range(rounds):
            acc = zeros(g)
            row_losses, total = [], 0.0
            for rows in client_rows:
                p, steps = copy(g), []
                for row in rows:
                    p, loss, count = sgd_step(p, jnp.asarray(row, jnp.int32))
                    steps.append((loss, count))
                loss_sum = sum(float(a) for a, _ in steps)
                counted = sum(float(b) for _, b in steps)
                acc = add_delta(acc, p, g, counted)
                total += counted
                row_losses.append(loss_sum / max(counted, 1.0))
            m = zeros(g) if m is None else jax.tree.map(jnp.asarray, m)
            g, m = server(g, m, acc, total)
            m = jax.tree.map(np.asarray, m) if r + 1 < rounds else None
            losses.append(row_losses)
    return np.asarray(losses, np.float64), jax.tree.map(np.asarray, g)
