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
* ``*``, grouped-query attention: ``num_attention_heads`` query heads of
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
* **Packed rows**, as ``fedtpu.models.olmoe``: a row is ``(2, T)`` token and
  segment ids, 0 marking padding. Attention stays within a document; the
  state-space layer's state is zero at a document's first token and its
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
* **The mixer's two float32 passes, two bodies each.** Between ``W_in``
  and the scan the convolution and its SiLU pass over ``xBC``, between the
  scan and ``W_out`` the ``D`` skip, the gate and the grouped norm over
  ``y``, ``x`` and ``z``: elementwise but for a window of four rows and a
  mean over a group's lanes. ``causal_conv`` and ``gated_group_norm`` are
  the definitions, the CPU's path and tier-1's, and autodiff's to
  differentiate. Where ``fused_passes_apply`` says the tiled bodies exist
  (a TPU, whole row tiles, widths and a group of whole lane tiles: shape and
  platform only) each pass is ONE row-tiled kernel forward and one backward
  under a differentiation rule of its own (``fedtpu.ops.ssm_passes``): the
  operands are read once, in place out of ``W_in``'s product and the
  convolution's output, every result is written once, the gate's in
  ``compute_dtype`` (the next operation cast it), the backward kernels
  recompute what they need in the tile and add up the weights' gradients
  across the row tiles. And they meet the scan in the form XLA keeps the
  scan's arrays in, a chunk's positions on the lanes: ``x`` leaves the
  convolution once more with the positions last, ``y`` enters the gate as
  the scan leaves it, the cotangents likewise, so no transposing copy
  stands between a kernel and the scan. Float32 as the definitions; only
  the order of the sums over rows differs. ``ssm_fused_passes`` counts the
  positions that ran them.
* **An expert layer that holds a share.** The layer is told ``experts_held``
  and ``first_expert``: it scores and selects over ALL routed experts and
  computes, droplessly, exactly the assignments of real tokens that fall on
  the experts it holds, and the shared expert for every token; what the
  absent experts would have added is left out. On one chip there is no
  exchange. The assignments are sorted held-first (``olmoe``'s
  ``sorted_assignments``) and the first ``rows`` of them go through
  ``olmoe.grouped_matmul``. A buffer has a static size, the worst case is
  every assignment and the mean is ``held / routed`` of them: so the buffer
  is one BLOCK of rows at 8/3 of the mean (``held_block_rows``) and a
  loop runs as many blocks as this step's held assignments fill, its trips
  read from the groups' sizes: one on nearly every step, all of them if
  every token chose only experts held here. Exact whatever the skew, and
  the worst case costs only when it happens. What the room costs is the
  body's to say: the tiled grouped kernels (a TPU at the published widths,
  ``olmoe.grouped_matmul_applies``) visit no tile past the last group, so
  the two products take time by the filled rows and the empty ones cost
  the dispatch's gathers and scatter-adds alone; the TPU's
  ``lax.ragged_dot`` takes time by the buffer's rows, filled or not (7 ms
  a layer and step for 4,096 rows more at those widths, PERF.md section 6,
  PR 32). The statistics count ``rows_computed`` against
  ``assignments_held``. A loop of that kind has no
  transpose, so the function has its own differentiation rule
  (``held_experts``): the backward pass runs the same trips and
  differentiates each block inside its trip, adding up the weights'
  gradients (one pass over them a block: the price of the static buffer).

Parameters are float32; ``compute_dtype`` is the dtype of every large
matmul's inputs, as in ``olmoe``. The head and its loss (``_head_loss``),
the attention core with its two bodies, ``grouped_matmul`` with its two and
the norms are ``olmoe``'s own functions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from fedtpu.models import olmoe
from fedtpu.models.olmoe import (ATTENTION, EMBED, EXPERT_DISPATCH, EXPERTS,
                                 INIT_STD, LM_HEAD_LOSS, RECOMPUTE, ROUTER,
                                 SHARED_EXPERT, SSM, SSM_CONV, SSM_GATE_NORM,
                                 SSM_IN_PROJ, SSM_OUT_PROJ, SSM_SCAN,
                                 _head_loss, attention_core, gather_rows,
                                 grouped_matmul, next_token_targets, rms_norm,
                                 sorted_assignments)
from fedtpu.ops import ssm_passes

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}
# The held assignments are computed in blocks of whole tiles of this many
# rows (olmoe.GROUPED_ROW_TILE, what the tiled grouped kernels need).
HELD_ROW_TILE = 256

_mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)


def layer_kinds(cfg) -> tuple:
    """The kind of every layer, in order, from the pattern's letters."""
    pattern = cfg.hybrid_override_pattern
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} has letters {unknown}: "
            f"the stack knows {sorted(KINDS)} (a plain MLP layer, '-', is "
            "not built)")
    if len(pattern) != cfg.num_hidden_layers:
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} has {len(pattern)} layers "
            f"and num_hidden_layers is {cfg.num_hidden_layers}")
    return tuple(KINDS[letter] for letter in pattern)


def experts_share(cfg) -> tuple:
    """``(experts held, first expert)`` of this chip; 0 held = all."""
    held = cfg.experts_held or cfg.n_routed_experts
    if not 0 <= cfg.first_expert <= cfg.n_routed_experts - held:
        raise ValueError(
            f"experts [{cfg.first_expert}, {cfg.first_expert + held}) are "
            f"not among the {cfg.n_routed_experts} the router scores")
    return held, cfg.first_expert


def held_block_rows(assignments: int, share: float) -> int:
    """The rows of one block of the held-assignments buffer, for
    ``assignments`` in all of which ``share`` are held on average: whole
    tiles, 8/3 of the mean. A layer's share moves with the draw of the
    router and the step's tokens (at the published widths a layer held 0.5
    to 1.8 of the mean over a seed's steps, which of the four by the seed):
    at a third over the mean most steps of some seeds took a second block
    and none of others', and a round's time moved by 4% with the seed."""
    tile = HELD_ROW_TILE
    return min(-(-assignments // tile) * tile,
               max(tile, -(-int(assignments * share * 8 / 3) // tile) * tile))


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


def _experts_init(cfg, normal, ones, key, dtype):
    h, i, s = (cfg.hidden_size, cfg.moe_intermediate_size,
               cfg.moe_shared_expert_intermediate_size)
    held, _ = experts_share(cfg)
    return {"norm": ones(h), "router": normal(h, cfg.n_routed_experts),
            "router_bias": normal(cfg.n_routed_experts),
            "up": normal(held, h, i), "down": normal(held, i, h),
            "shared_up": normal(h, s), "shared_down": normal(s, h)}


_INITS = {"mamba": _mamba_init, "attention": _attention_init,
          "experts": _experts_init}


def nemotron_h_init(key: jax.Array, cfg, param_dtype=jnp.float32):
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
def document_runs(segs):
    """``(run (T,) int32, starts (T,) bool)``: the index of the run of equal
    segment ids each position lies in (1, 2, ...), and where a run starts.
    The state-space layer restarts at every start, padding's run included."""
    starts = jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])
    return jnp.cumsum(starts.astype(jnp.int32)), starts


def causal_conv(x, w, b, run):
    """Depthwise causal convolution of ``x (T, C)`` with ``w (K, C)``, ``w[j]``
    weighing the position ``K - 1 - j`` back, over the positions of the same
    run only: a document's first tokens see zeros before them."""
    taps, t = w.shape[0], x.shape[0]
    out = x * w[taps - 1] + b
    for back in range(1, taps):
        earlier = jnp.pad(x, ((back, 0), (0, 0)))[:t]
        same = jnp.pad(run, (back, 0))[:t] == run       # run ids start at 1
        out = out + jnp.where(same[:, None], earlier, 0.0) * w[taps - 1 - back]
    return out


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


def gated_group_norm(y, z, gain, groups: int, eps):
    """``gain * RMSNorm(y * silu(z))``, the norm over each of ``groups``
    equal parts of the last axis; float32."""
    y = y * jax.nn.silu(z)
    parts = y.reshape(y.shape[0], groups, -1)
    parts = parts * lax.rsqrt(jnp.mean(parts * parts, axis=-1, keepdims=True)
                              + eps)
    return parts.reshape(y.shape) * gain


def fused_passes_apply(cfg, t: int) -> bool:
    """Whether the tiled bodies of the mixer's two float32 passes
    (``fedtpu.ops.ssm_passes``: the convolution under its SiLU; the skip,
    the gate and the grouped norm) exist for a sequence of ``t`` positions
    where the program is being built: a TPU (the PROCESS's backend, as
    ``olmoe.fused_attention_applies`` reads it), ``t`` whole row tiles, and
    the inner width, ``xBC``'s width and a group of the norm whole lane
    tiles. ``causal_conv`` and ``gated_group_norm`` are the definitions and
    the body everywhere else."""
    width = cfg.mamba_num_heads * cfg.mamba_head_dim
    return jax.default_backend() == "tpu" and ssm_passes.tiles_apply(
        t, min(cfg.chunk_size, t), cfg.conv_kernel, width,
        width + 2 * cfg.n_groups * cfg.ssm_state_size, width, cfg.n_groups)


def mamba_mixer(cfg, compute_dtype, h, layer, segs):
    """``(mixer(RMSNorm(h)), statistics)`` of one ``M`` layer."""
    t = h.shape[0]
    heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, n = cfg.n_groups, cfg.ssm_state_size
    width, state = heads * p, groups * n
    cast = lambda arr: arr.astype(compute_dtype)
    run, starts = document_runs(segs)
    fused = fused_passes_apply(cfg, t)
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
                xbc = jax.nn.silu(causal_conv(xbc, layer["conv_w"],
                                              layer["conv_b"], run))
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
                y = gated_group_norm(y, z, layer["gate_norm"], groups,
                                     cfg.layer_norm_epsilon)
        with jax.named_scope(SSM_OUT_PROJ):
            out = _mm(cast(y), cast(layer["out_proj"]))
    real = segs > 0
    return out, {"ssm_positions": jnp.float32(t),
                 "ssm_restarts": (starts & real).sum().astype(jnp.float32)}


# ------------------------------------------------------------- attention
def attention_mixer(cfg, compute_dtype, h, layer, segs):
    """``(mixer(RMSNorm(h)), {})`` of one ``*`` layer: query head ``i``
    attends key-value head ``i // (heads / kv heads)``; no positions."""
    t = h.shape[0]
    heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
    cast = lambda arr: arr.astype(compute_dtype)
    with jax.named_scope(ATTENTION):
        x = cast(rms_norm(h, layer["norm"], cfg.layer_norm_epsilon))
        q = _mm(x, cast(layer["q"])).reshape(t, heads, hd)
        # the core's bodies take one head count: each key-value head is
        # repeated for the query heads that share it
        k, v = (jnp.repeat(_mm(x, cast(layer[name])).reshape(t, kv, hd),
                           heads // kv, axis=1) for name in ("k", "v"))
        ctx = attention_core(q, k, v, segs, compute_dtype)
        out = _mm(cast(ctx.reshape(t, heads * hd)), cast(layer["o"]))
    return out, {}


# --------------------------------------------------------------- experts
def route(x, router_w, bias, top_k: int, norm_topk_prob: bool, scale: float):
    """``(gates (T, k) float32, experts (T, k) int32)``: sigmoid scores over
    every expert in float32; the top k of ``score + bias`` are chosen and
    weigh by their SCORE, renormalised and scaled. ``bias`` only picks."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores + lax.stop_gradient(
        bias.astype(jnp.float32)), top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        gates = gates / (gates.sum(axis=-1, keepdims=True) + 1e-20)
    return gates * scale, experts.astype(jnp.int32)


def _activation(products):
    """An expert's activation from its first matmuls' products: ``relu(up)^2``
    of one (this tower's), ``silu(gate) * up`` of two (the gated form)."""
    if len(products) == 1:
        return jnp.square(jax.nn.relu(products[0]))
    gate, up = products
    return jax.nn.silu(gate) * up


def _held_block(x, weights, gates, order, sizes, block, rows: int,
                per_token: int, compute_dtype):
    """What rows ``[block * rows, (block + 1) * rows)`` of the sorted
    assignments add to the layer's output, ``(T, H)`` float32: the held
    assignments among them, each its expert's output times its gate."""
    cast = lambda arr: arr.astype(compute_dtype)
    start = block * rows
    with jax.named_scope(EXPERT_DISPATCH):
        taken = lax.dynamic_slice_in_dim(order, start, rows)
        # the groups' rows that fall inside this block
        ends = jnp.cumsum(sizes)
        inside = (jnp.clip(ends, start, start + rows)
                  - jnp.clip(ends - sizes, start, start + rows))
        # rows past the block's last group: ``lax.ragged_dot`` defines their
        # output as zero, but the TPU's kernel (and the tiled one) visits no
        # row past the last group and leaves there what memory held, forward
        # and in both gradients. Each product's rows are cut to the filled
        # ones by a select (which a stray infinity cannot pass, as a product
        # with zero would), going in and coming out, so that the transposes
        # cut them too.
        filled = (jnp.arange(rows) < inside.sum())[:, None]
        only_filled = lambda rows_: jnp.where(filled, rows_, 0)
        xs = only_filled(gather_rows(cast(x), taken, per_token))
        weigh = jnp.take(gates, taken)
    with jax.named_scope(EXPERTS):
        *into, down = weights
        act = _activation([only_filled(grouped_matmul(xs, cast(w), inside))
                           for w in into])
        ys = only_filled(grouped_matmul(cast(act), cast(down), inside))
    with jax.named_scope(EXPERT_DISPATCH):
        return jnp.zeros(x.shape, jnp.float32).at[taken // per_token].add(
            ys * weigh[:, None])


def held_blocks(sizes, rows: int):
    """How many blocks of ``rows`` the held assignments fill."""
    return (sizes.sum() + rows - 1) // rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def held_experts(x, weights, gates, order, sizes, rows: int, per_token: int,
                 compute_dtype):
    """``sum over held assignments of gate * expert_e(x)``, ``(T, H)``
    float32. ``x (T, H)`` float32; ``weights`` the held experts' matrices,
    ``(up (held, H, I), down (held, I, H))`` of ``down relu(up x)^2`` or
    ``(gate, up, down)`` of the gated ``down (silu(gate x) * up x)``;
    ``gates (T * per_token,)`` every assignment's gate,
    token-major; ``order`` (padded to whole blocks), ``sizes (held,)`` from
    ``sorted_assignments`` with the held assignments first. A loop over as
    many blocks of ``rows`` as hold them, its trips read from ``sizes``:
    reverse mode only, under a rule of its own, because a loop of that kind
    has no transpose."""
    block = functools.partial(_held_block, x, weights, gates, order, sizes,
                              rows=rows, per_token=per_token,
                              compute_dtype=compute_dtype)
    return lax.fori_loop(0, held_blocks(sizes, rows),
                         lambda i, out: out + block(i),
                         jnp.zeros(x.shape, jnp.float32))


def _held_experts_fwd(x, weights, gates, order, sizes, rows, per_token,
                      compute_dtype):
    out = held_experts(x, weights, gates, order, sizes, rows, per_token,
                       compute_dtype)
    return out, (x, weights, gates, order, sizes)


def _held_experts_bwd(rows, per_token, compute_dtype, residuals, g):
    x, weights, gates, order, sizes = residuals

    def step(i, grads):
        # a block is differentiated inside its own trip: what it keeps for
        # its backward pass lives and dies there
        with jax.named_scope(RECOMPUTE):
            _, pull = jax.vjp(
                lambda *primals: _held_block(
                    *primals, order, sizes, i, rows=rows, per_token=per_token,
                    compute_dtype=compute_dtype), x, weights, gates)
        return jax.tree.map(jnp.add, grads, pull(g))

    grads = lax.fori_loop(0, held_blocks(sizes, rows), step,
                          jax.tree.map(jnp.zeros_like, (x, weights, gates)))
    return (*grads, None, None)


held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def experts_mixer(cfg, compute_dtype, h, layer, segs, eps=None):
    """``(mixer(RMSNorm(h)), statistics)`` of one ``E`` layer: this chip's
    share of the routed sum, and the shared expert. A layer that has
    ``gate`` and ``shared_gate`` beside ``up`` and ``down`` holds gated
    experts (``fedtpu.models.xing4``'s), one without them this tower's;
    ``eps`` is the pre-norm's where it is not this tower's."""
    t = h.shape[0]
    top_k, routed = cfg.num_experts_per_tok, cfg.n_routed_experts
    held, first_expert = experts_share(cfg)
    cast = lambda arr: arr.astype(compute_dtype)
    gated = "gate" in layer
    with jax.named_scope(ROUTER):
        x = rms_norm(h, layer["norm"],
                     cfg.layer_norm_epsilon if eps is None else eps)
        gates, experts = route(x, layer["router"], layer["router_bias"], top_k,
                               cfg.norm_topk_prob, cfg.routed_scaling_factor)
    with jax.named_scope(EXPERT_DISPATCH):
        # an assignment's group: the held expert's own index, or one past
        # them for an expert that lives elsewhere and for padding, which is
        # routed nowhere; sorted, the held ones come first
        flat = experts.reshape(-1)
        real = jnp.repeat(segs > 0, top_k)
        local = flat - first_expert
        here = real & (local >= 0) & (local < held)
        order, sizes = sorted_assignments(jnp.where(here, local, held),
                                          held + 1)
        sizes = sizes[:held]
        load = jnp.zeros((routed,), jnp.int32).at[flat].add(
            real.astype(jnp.int32))
        rows = held_block_rows(t * top_k, held / routed)
        total, computed = sizes.sum(), held_blocks(sizes, rows) * rows
        # counted, not derived: the held assignments the sort put inside
        # the blocks that are computed (all of them, or something is broken)
        covered = (jnp.take(here, order)
                   & (jnp.arange(order.shape[0]) < computed)).sum()
        order = jnp.pad(order, (0, -order.shape[0] % rows))
    weights = ((layer["gate"], layer["up"], layer["down"]) if gated
               else (layer["up"], layer["down"]))
    out = held_experts(x, weights, gates.reshape(-1), order, sizes, rows,
                       top_k, compute_dtype)
    with jax.named_scope(SHARED_EXPERT):
        xc = cast(x)
        into = ("shared_gate", "shared_up") if gated else ("shared_up",)
        act = _activation([_mm(xc, cast(layer[name])) for name in into])
        out = out + _mm(cast(act), cast(layer["shared_down"]))
    return out, {"expert_load": load,
                 "assignments_held": total.astype(jnp.float32),
                 "rows_computed": computed.astype(jnp.float32),
                 "rows_held_computed": covered.astype(jnp.float32)}


_MIXERS = {"mamba": mamba_mixer, "attention": attention_mixer,
           "experts": experts_mixer}


# ------------------------------------------------------------- the model
def _zero_stats(cfg):
    zero = jnp.float32(0.0)
    return {"ssm_positions": zero, "ssm_restarts": zero,
            "expert_load": jnp.zeros((cfg.n_routed_experts,), jnp.int32),
            "assignments_held": zero, "rows_computed": zero,
            "rows_held_computed": zero}


def nemotron_h_sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: ``olmoe_sequence_stats``'s
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
    core = jax.ShapeDtypeStruct((t, cfg.num_attention_heads, cfg.head_dim),
                                compute_dtype)
    # the rules between the bodies, read as olmoe's own callers read them
    fused = ("attention" in kinds
             and olmoe.fused_attention_applies(core, core, core))
    held, _ = experts_share(cfg)
    rows = held_block_rows(t * cfg.num_experts_per_tok,
                           held / cfg.n_routed_experts)
    wide, narrow = cfg.hidden_size, cfg.moe_intermediate_size
    grouped = "experts" in kinds and all(olmoe.grouped_matmul_applies(
        jax.ShapeDtypeStruct((rows, k), compute_dtype),
        jax.ShapeDtypeStruct((held, k, n), compute_dtype))
        for k, n in ((wide, narrow), (narrow, wide)))
    tiled = "mamba" in kinds and fused_passes_apply(cfg, t)
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
            **olmoe.attention_blocks(segs, fused, kinds.count("attention")),
            **stats}


def nemotron_h_stats(params, x, mask, cfg, compute_dtype=jnp.float32):
    """``nemotron_h_sequence_stats`` summed over the rows ``x (N, 2, T)``
    whose ``mask`` is 1, one row at a time."""
    def one(row_and_mask):
        row, m = row_and_mask
        stats = nemotron_h_sequence_stats(
            params, row * m.astype(row.dtype), cfg, compute_dtype)
        return {**stats, **{k: stats[k] * m for k in (
            "padding", "fused_attention", "grouped_experts",
            "attention_blocks_computed", "attention_blocks_causal",
            "ssm_fused_passes", "ssm_positions", "rows_computed")}}

    if x.shape[0] == 1:
        return one((x[0], mask[0]))
    stats = lax.map(one, (x, mask))
    return jax.tree.map(lambda a: a.sum(axis=0), stats)
