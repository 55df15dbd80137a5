"""Nemotron-H: a decoder stack of three kinds of layer on packed sequences.

The tower is the one ``config.json`` of nvidia's Nemotron-Labs-TwoTower-30B-
A3B-Base-BF16 (``model_type: nemotron_h``) defines. Every layer is ONE mixer
behind one pre-norm, ``h <- h + mixer(RMSNorm(h))``, and the letter of
``hybrid_override_pattern`` at the layer's place says which:

* ``M``, a Mamba-2 mixer. ``[z | xBC | dt] = x W_in``; ``xBC`` passes a
  causal depthwise convolution over the last ``conv_kernel`` positions and a
  SiLU, and splits into ``x`` (heads x head_dim), ``B`` and ``C`` (``n_groups``
  x ``ssm_state_size`` each; a head reads the group ``head // (heads /
  groups)``). ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``. A head's
  state is ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` and its output
  ``y_t = S_t C_t + D x_t``; then ``w * RMSNorm(y * silu(z))`` over each of
  the ``n_groups`` groups of the inner width (gate first, then the norm) and
  ``W_out``. No bias but the convolution's.
* ``*``, grouped-query attention (``fedtpu.models.layers.attention_mixer``,
  shared with the delta-rule stack): ``num_attention_heads`` query heads of
  ``head_dim`` (NOT ``hidden_size / heads``) over ``num_key_value_heads``
  key-value heads, causal, no positional encoding of any kind (positions
  reach the model through the state-space layers).
* ``E``, sparse experts beside a shared one. Scores are the SIGMOID of the
  router's logits over all ``n_routed_experts`` in float32; the top
  ``num_experts_per_tok`` of ``score + bias`` are chosen (``router_bias``, a
  leaf no gradient reaches: it only picks); their scores are renormalised
  (``norm_topk_prob``) and scaled by ``routed_scaling_factor``. An expert is
  ``W_down relu(W_up x)^2`` (two matmuls, no gate), and one shared expert of
  its own width takes every token.

After the last layer a final RMSNorm and an untied linear head.

What is this repo's own:

* **A stack of kinds.** The pattern is data: ``layer_kinds`` reads it, the
  layers run unrolled in its order, and each kind's parameters lie under
  its own subtree (``params["mamba"][i]`` is the i-th ``M`` layer's), a leaf
  a layer and nothing stacked, so a layer's gradient is applied and let go
  where it is made. Every layer is recomputed from its input in the backward
  pass (``jax.checkpoint`` a layer): one ``(T, hidden)`` array a layer is
  kept, and one layer's intermediates are alive at a time. (Unrolled, the
  compiled program holds every layer once a kind of the engine's steps. A
  ``lax.scan`` over blocks of one period of the pattern was built and
  measured, and lost: the compiler casts the whole stacked weights before
  the scan and the stacked gradient lives whole. PERF.md section 6, PR 32.)
* **Packed rows**: a row is ``(2, T)`` token and segment ids, 0 marking
  padding. Attention stays within a document; the state-space layer's state
  is zero at a document's first token and its
  convolution does not read across the edge; the loss leaves out padding
  and each document's last token. Two documents packed into one row give
  what the two alone give.
* **The chunked scan.** ``ssd_scan`` runs the recurrence in chunks of
  ``chunk_size``: inside a chunk the output is a masked matmul (``C B^T``
  times the decay from source to target), each chunk's contribution to the
  state is a matmul, a ``lax.scan`` carries the state from chunk to chunk,
  and a matmul reads the incoming state. A document's edge sets the decay
  across it to zero in all three places. Forward and backward are XLA's
  (autodiff through the above). ``dt``, ``A``, the decays, their cumulative
  sums, the state carry and the norm are float32; the chunk products take
  ``compute_dtype`` inputs and sum in float32.
* **The mixer's two float32 passes** (the convolution under its SiLU; the
  ``D`` skip, the gate and the grouped norm) have two bodies each and the
  rule between them in ``fedtpu.ops.ssm_passes``. Where the tiled bodies run
  they meet the scan in XLA's own form of the scan's arrays (``x`` once more
  with the positions last, ``y`` as the scan leaves it).
  ``ssm_fused_passes`` counts the positions that ran them.
* **An expert layer that holds a share**: ``fedtpu.models.layers.
  experts_mixer`` (the held-first sort, the blocks of the static buffer and
  the differentiation rule are described there), here with ``relu^2``
  experts.

Parameters are float32; ``compute_dtype`` is the dtype of every large
matmul's inputs. The head and its loss (``fedtpu.ops.lm_head``), the
attention core with its two bodies (``fedtpu.ops.packed_attention``),
``grouped_matmul`` with its two and the norm are shared with the other
language models.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from fedtpu.models.layers import (INIT_STD, _experts_init, attention_mixer,
                                  bodies_at, experts_mixer, experts_share,
                                  held_matmuls, rms_norm)
from fedtpu.ops import ssm_passes
from fedtpu.ops.lm_head import _head_loss, next_token_targets
from fedtpu.ops.packed_attention import attention_blocks
from fedtpu.ops.scopes import (EMBED, LM_HEAD_LOSS, SSM, SSM_CONV,
                               SSM_GATE_NORM, SSM_IN_PROJ, SSM_OUT_PROJ,
                               SSM_SCAN)

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}

# what counts a row, not its tokens: a padded row's is left out (rows_stats)
PER_ROW = ("padding", "fused_attention", "grouped_experts",
           "attention_blocks_computed", "attention_blocks_causal",
           "ssm_fused_passes", "ssm_positions", "rows_computed")

_mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)


def layer_kinds(cfg) -> tuple:
    """The kind of every layer, in order, from the pattern's letters."""
    pattern = cfg.hybrid_override_pattern
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} has letters {unknown}: "
            f"the stack knows {sorted(KINDS)}: one mixer a layer. (A plain "
            "MLP layer, '-', is not built in THIS stack; a model whose every "
            "layer is a mixer and a feed-forward is kind='phi4_flash'.)")
    if len(pattern) != cfg.num_hidden_layers:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} has {len(pattern)} layers "
            f"and num_hidden_layers is {cfg.num_hidden_layers}")
    return tuple(KINDS[letter] for letter in pattern)


def check(cfg) -> None:
    """What the pattern, the share and the widths must satisfy."""
    layer_kinds(cfg)            # the pattern's letters and its length
    experts_share(cfg)
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        raise ValueError(
            f"{cfg.num_attention_heads} query heads do not divide over "
            f"{cfg.num_key_value_heads} key-value heads")
    if cfg.mamba_num_heads % cfg.n_groups:
        raise ValueError(
            f"{cfg.mamba_num_heads} state-space heads do not divide "
            f"into {cfg.n_groups} groups")


# ------------------------------------------------------------------ init
def _mamba_init(cfg, normal, ones, key, dtype):
    """The mixer's own leaves as ``Mamba2PreTrainedModel._init_weights``
    draws them: ``A_log = log(1..heads)``, ``D = 1``, ``dt_bias`` the
    inverse softplus of a log-uniform step in ``[time_step_min,
    time_step_max]``, the convolution's weight PyTorch's default (uniform,
    bound ``kernel^-1/2``) and its bias zero."""
    heads, width = cfg.mamba_num_heads, cfg.mamba_num_heads * cfg.mamba_head_dim
    state = cfg.n_groups * cfg.ssm_state_size
    k_dt, k_conv = jax.random.split(key)
    dt = jnp.exp(jax.random.uniform(k_dt, (heads,), jnp.float32)
                 * (jnp.log(cfg.time_step_max) - jnp.log(cfg.time_step_min))
                 + jnp.log(cfg.time_step_min))
    dt = jnp.maximum(dt, cfg.time_step_floor)
    bound = cfg.conv_kernel ** -0.5
    return {
        "norm": ones(cfg.hidden_size),
        "in_proj": normal(cfg.hidden_size, 2 * width + 2 * state + heads),
        "conv_w": jax.random.uniform(
            k_conv, (cfg.conv_kernel, width + 2 * state), dtype, -bound, bound),
        "conv_b": jnp.zeros((width + 2 * state,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)).astype(dtype),
        "D": ones(heads),
        "gate_norm": ones(width),
        "out_proj": normal(width, cfg.hidden_size),
    }


def _attention_init(cfg, normal, ones, key, dtype):
    h, q, kv = (cfg.hidden_size, cfg.num_attention_heads * cfg.head_dim,
                cfg.num_key_value_heads * cfg.head_dim)
    return {"norm": ones(h), "q": normal(h, q), "k": normal(h, kv),
            "v": normal(h, kv), "o": normal(q, h)}


_INITS = {"mamba": _mamba_init, "attention": _attention_init,
          "experts": _experts_init}


def init(key: jax.Array, cfg, param_dtype=jnp.float32):
    """N(0, 0.02) weights (``initializer_range``) and selection biases, unit
    norm gains, the state-space leaves as ``_mamba_init`` says. Each kind's
    layers are a tuple under the kind's name, in the pattern's order."""
    kinds = layer_kinds(cfg)
    keys = iter(jax.random.split(key, 2 + 8 * len(kinds)))

    def normal(*shape):
        return INIT_STD * jax.random.normal(next(keys), shape, param_dtype)

    ones = lambda *shape: jnp.ones(shape, param_dtype)
    params = {"embed": normal(cfg.vocab_size, cfg.hidden_size),
              **{kind: [] for kind in KINDS.values()}}
    for kind in kinds:
        params[kind].append(_INITS[kind](cfg, normal, ones, next(keys),
                                         param_dtype))
    params.update({kind: tuple(params[kind]) for kind in KINDS.values()},
                  final_norm=ones(cfg.hidden_size),
                  head=normal(cfg.hidden_size, cfg.vocab_size))
    return params


# --------------------------------------------------------------- mamba-2
def ssd_scan(x, dt, a, b, c, run, chunk: int, compute_dtype):
    """``y (T, heads, P)`` float32, ``y_t = S_t C_t`` of the recurrence
    ``S_t = exp(dt_t a) S_{t-1} [t-1 in t's run] + dt_t x_t (x) B_t``, in
    chunks. ``x (T, heads, P)``, ``dt (T, heads)`` after its softplus,
    ``a (heads,)`` negative, ``b``, ``c`` ``(T, groups, N)``, ``run (T,)``
    from ``document_runs``; ``T`` is whole chunks (or one shorter chunk)."""
    t, heads, p = x.shape
    groups, n = b.shape[1:]
    per = heads // groups
    q = min(chunk, t)
    if t % q:
        raise ValueError(f"a sequence of {t} positions is not whole chunks "
                         f"of {q}")
    k = t // q
    cast = lambda arr: arr.astype(compute_dtype)
    f32 = dict(preferred_element_type=jnp.float32)
    # cumulative log-decay within a chunk, heads before positions: the
    # (l, s) planes below then have whole lanes
    cs = jnp.cumsum((dt * a).reshape(k, q, heads), axis=1).transpose(0, 2, 1)
    runs = run.reshape(k, q)
    last = runs[:, -1]
    before = jnp.concatenate([jnp.zeros((1,), run.dtype), last[:-1]])
    xdt = (x * dt[..., None]).reshape(k, q, groups, per, p)
    bk, ck = b.reshape(k, q, groups, n), c.reshape(k, q, groups, n)

    # inside a chunk: target l reads source s <= l of its own run
    idx = jnp.arange(q)
    allowed = ((idx[:, None] >= idx[None, :])[None]
               & (runs[:, :, None] == runs[:, None, :]))            # (k, l, s)
    decay = jnp.exp(jnp.where(allowed[:, None],
                              cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))                            # (k, h, l, s)
    scores = jnp.einsum("klgn,ksgn->kgls", cast(ck), cast(bk), **f32)
    weights = scores[:, :, None] * decay.reshape(k, groups, per, q, q)
    y = jnp.einsum("kgrls,ksgrp->klgrp", cast(weights), cast(xdt), **f32)

    # what a chunk adds to the state at its end: sources of the last run
    to_end = (jnp.exp(cs[..., -1:] - cs)
              * (runs == last[:, None])[:, None])                   # (k, h, s)
    weighted = xdt * to_end.transpose(0, 2, 1).reshape(k, q, groups, per, 1)
    added = jnp.einsum("ksgrp,ksgn->kgrpn", cast(weighted), cast(bk), **f32)

    # from chunk to chunk: the state survives a chunk that is all one run
    # with the chunk before it
    keep = (jnp.exp(cs[..., -1]) * (last == before)[:, None]).reshape(
        k, groups, per, 1, 1)

    def carry(state, step):
        kept, new = step
        return kept * state + new, state

    _, entering = lax.scan(carry, jnp.zeros(added.shape[1:], jnp.float32),
                           (keep, added))

    # reading the entering state: targets of the run it belongs to
    from_start = jnp.exp(cs) * (runs == before[:, None])[:, None]   # (k, h, l)
    read = jnp.einsum("klgn,kgrpn->klgrp", cast(ck), cast(entering), **f32)
    y = y + read * from_start.transpose(0, 2, 1).reshape(k, q, groups, per, 1)
    return y.reshape(t, heads, p)


def mamba_mixer(cfg, compute_dtype, h, layer, segs):
    """``(mixer(RMSNorm(h)), statistics)`` of one ``M`` layer."""
    t = h.shape[0]
    heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, n = cfg.n_groups, cfg.ssm_state_size
    width, state = heads * p, groups * n
    cast = lambda arr: arr.astype(compute_dtype)
    run, starts = ssm_passes.document_runs(segs)
    fused = ssm_passes.fused_passes_apply(cfg, t)
    with jax.named_scope(SSM):
        with jax.named_scope(SSM_IN_PROJ):
            x = cast(rms_norm(h, layer["norm"], cfg.layer_norm_epsilon))
            proj = _mm(x, cast(layer["in_proj"]))
            z, xbc, dt = jnp.split(proj, [width, 2 * width + 2 * state],
                                   axis=-1)
        with jax.named_scope(SSM_CONV):
            if fused:   # ``xBC`` read out of the product in place; ``x``
                # comes once more with the positions last, the scan's form
                xbc, xs = ssm_passes.conv_silu(
                    proj, layer["conv_w"], layer["conv_b"], run, width,
                    width + 2 * state, width)
                _, b, c = jnp.split(xbc, [width, width + state], axis=-1)
                xs = xs.T
            else:
                xbc = jax.nn.silu(ssm_passes.causal_conv(
                    xbc, layer["conv_w"], layer["conv_b"], run))
                xs, b, c = jnp.split(xbc, [width, width + state], axis=-1)
            xs = xs.reshape(t, heads, p)
            dt = jax.nn.softplus(dt + layer["dt_bias"])
        with jax.named_scope(SSM_SCAN):
            y = ssd_scan(xs, dt, -jnp.exp(layer["A_log"].astype(jnp.float32)),
                         b.reshape(t, groups, n), c.reshape(t, groups, n),
                         run, cfg.chunk_size, compute_dtype)
        with jax.named_scope(SSM_GATE_NORM):
            if fused:   # ``y`` as the scan leaves it, ``x`` and ``z`` in
                # place; rounded here, once
                y = ssm_passes.skip_gate_norm(
                    ssm_passes.chunk_transposed(y.reshape(t, width),
                                                min(cfg.chunk_size, t)),
                    xbc, proj, layer["D"], layer["gate_norm"], groups,
                    cfg.layer_norm_epsilon, compute_dtype)
            else:
                y = (y + layer["D"][:, None] * xs).reshape(t, width)
                y = ssm_passes.gated_group_norm(
                    y, z, layer["gate_norm"], groups, cfg.layer_norm_epsilon)
        with jax.named_scope(SSM_OUT_PROJ):
            out = _mm(cast(y), cast(layer["out_proj"]))
    real = segs > 0
    return out, {"ssm_positions": jnp.float32(t),
                 "ssm_restarts": (starts & real).sum().astype(jnp.float32)}


_MIXERS = {"mamba": mamba_mixer, "attention": attention_mixer,
           "experts": experts_mixer}


# ------------------------------------------------------------- the model
def _zero_stats(cfg):
    zero = jnp.float32(0.0)
    return {"ssm_positions": zero, "ssm_restarts": zero,
            "expert_load": jnp.zeros((cfg.n_routed_experts,), jnp.int32),
            "assignments_held": zero, "rows_computed": zero,
            "rows_held_computed": zero}


def sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: every language model's
    sums over tokens (``loss_sum``, ``correct``, ``count``, ``tokens``,
    ``padding``, ``expert_load`` over ALL routed experts and summed over
    layers, ``fused_attention``, ``grouped_experts``), ``ssm_fused_passes``
    (positions whose ``M`` layers ran the tiled passes: T or 0) and the
    share's and the scan's own, summed over layers: ``assignments_held``
    (real tokens'
    assignments on held experts), ``rows_computed`` (the buffer the expert
    matmuls ran over), ``rows_held_computed`` (held assignments inside it:
    all of them), ``ssm_positions`` (positions the scan ran over),
    ``ssm_restarts`` (documents whose state started at zero)."""
    tokens, segs = row[0], row[1]
    kinds = layer_kinds(cfg)
    t = tokens.shape[0]
    _, fused, grouped = bodies_at(
        t, cfg.num_attention_heads, cfg.head_dim, cfg.head_dim, compute_dtype,
        experts=held_matmuls(cfg, t) if "experts" in kinds else None)
    fused = "attention" in kinds and fused
    tiled = "mamba" in kinds and ssm_passes.fused_passes_apply(cfg, t)
    with jax.named_scope(EMBED):
        h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def layer_step(kind, h, layer):
        out, stats = _MIXERS[kind](cfg, compute_dtype, h, layer, segs)
        return h + out, stats

    stats, seen = _zero_stats(cfg), dict.fromkeys(KINDS.values(), 0)
    for kind in kinds:
        # recomputed from its input in the backward pass: one (T, H) array
        # a layer is kept
        h, own = jax.checkpoint(functools.partial(layer_step, kind))(
            h, params[kind][seen[kind]])
        seen[kind] += 1
        stats = {**stats, **{k: stats[k] + v for k, v in own.items()}}
    with jax.named_scope(LM_HEAD_LOSS):
        labels, valid = next_token_targets(tokens, segs)
        h = rms_norm(h, params["final_norm"], cfg.layer_norm_epsilon)
        loss, correct = _head_loss(h, params["head"], labels, valid,
                                   compute_dtype)
    return {"loss_sum": loss, "correct": correct, "count": valid.sum(),
            "tokens": (segs > 0).sum().astype(jnp.float32),
            "padding": (segs == 0).sum().astype(jnp.float32),
            "fused_attention": jnp.float32(t if fused else 0),
            "grouped_experts": jnp.float32(t if grouped else 0),
            "ssm_fused_passes": jnp.float32(t if tiled else 0),
            **attention_blocks(segs, fused, kinds.count("attention")),
            **stats}
