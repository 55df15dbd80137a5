"""OLMoE: a sparse-expert decoder language model on packed sequences.

The block is allenai's OLMoE-1B-7B as its ``config.json`` and modelling code
state it: pre-norm residual layers; multi-head attention whose projected
queries and keys pass an RMSNorm over the WHOLE projection (before the split
into heads), then RoPE over every dimension of a head in the rotate-half
convention; a router that takes the softmax of its logits over all experts in
float32, keeps the top ``num_experts_per_tok`` and (``norm_topk_prob`` false)
does NOT renormalise them; SiLU-gated experts ``down(silu(gate(x)) * up(x))``;
a final RMSNorm and an untied linear head. No biases anywhere.

What is this repo's own:

* **Packed rows.** An input row is ``(2, T)`` int32: token ids and segment
  ids, 0 marking padding. Attention is causal within a segment, positions
  restart at each segment, and the next-token loss is masked at padding and
  at the last token of each document, so two documents packed into one row
  give the losses and gradients of the two alone.
* **Dropless experts.** Every (token, expert) assignment is computed: the
  assignments are sorted by expert and the three expert matmuls run as
  grouped matmuls over the uneven groups (``grouped_matmul``). There is
  no capacity factor and no auxiliary loss (HF's default
  ``output_router_logits=False`` computes none).
* **A loss that never holds the logits whole and never computes them
  twice.** The head and the cross-entropy run over chunks of the sequence:
  one chunk's ``[chunk, vocab]`` float32 logits exist at a time. The loss
  is the model's last operation, so the function has its own
  differentiation rule (``jax.custom_vjp``): the forward pass of a chunk
  takes the loss's gradient from the logits it has and runs both gradient
  matmuls there; the backward pass scales the result by the scalar
  cotangent. Three matmuls over the vocabulary a chunk, where a
  checkpointed scan ran four.
* **Scanned layers.** Layer parameters carry a leading layers axis and the
  stack is a ``lax.scan``, so depth 16 compiles as depth 1 does.
* **An attention core with two bodies.** ``softmax(mask(q k^T / sqrt(d))) v``
  is one function of ``(q, k, v, segs)``. Its XLA body is the definition:
  it writes the ``[heads, T, T]`` float32 scores, the masked scores and the
  probabilities to memory and keeps them for the backward pass. Its fused
  body is three tiled kernels with an online softmax
  (``fedtpu.ops.packed_attention``: the bodies of the library's
  ``jax.experimental.pallas.ops.tpu.flash_attention``, causal, segment ids,
  its own backward), which never hold a ``[heads, T, T]`` array: the same
  mask, bf16 matmul inputs, float32 accumulation, maximum, sum and
  exponentials. What the fused body leaves out, beside the blocks above the
  diagonal, are the (query block, key block) pairs that lie wholly across
  two documents: it decides from the row's own segment ids on the device,
  the least and largest id of each block, and runs a pair only where the
  two blocks' ranges overlap, so a row of one document runs every pair and
  a row of fourteen runs a third of them (``attention_blocks`` counts both).
  Which body runs is read off what the code can see and is
  nobody's to set (``fused_attention_applies``): the fused body when the
  program is built for a TPU, the head width is a multiple of 128 lanes,
  ``T`` a multiple of the kernel's block and q, k and v share one width
  (a head whose query-key and value widths differ is padded with zeros to
  one, ``padded_head_width``, and its context cut back); the XLA body
  everywhere else (the CPU, the tests' tiny shapes). The fused backward
  takes its row term ``sum(o * do)`` from the bf16 ``ctx`` and feeds bf16
  ``dS`` to its matmuls, where the XLA body's softmax backward is float32
  throughout:
  within "bf16 matmul inputs", and measured inside the benchmark's limits
  (PERF.md section 6, PR 26). The sequence statistics say how many
  positions ran fused (``fused_attention``) and how many block pairs it ran
  of those on or under the diagonal (``attention_blocks_computed``,
  ``attention_blocks_causal``).

* **Expert matmuls with two bodies.** ``grouped_matmul(xs, w, sizes)`` is
  one function too. Its XLA body, ``lax.ragged_dot``, is the definition,
  and what the TPU's compiler makes of it runs at 27-35% of the MXU's peak
  on these uneven groups. Its Pallas body is the library's tiled grouped
  matmul (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward,
  ``gmm`` on the weight in place for the input's gradient, ``tgmm`` for
  the weight's) at tiles chosen on the chip, 52-61% of the peak: the same
  bf16 operands and float32 sums, gradients rounded to bf16 once, where
  autodiff rounded the XLA body's float32 ones. ``grouped_matmul_applies``
  picks, as for the attention core and as little anybody's to set: the
  kernels on a TPU at bf16 operands, whole row tiles and the widths that
  have tiles from a sweep on the chip (1024 and 2048; the hybrid stack's
  2688 x 1856); ``lax.ragged_dot`` everywhere else. The sequence
  statistics say how many positions ran in the kernels
  (``grouped_experts``).

Parameters are float32. ``compute_dtype`` (bfloat16 in the shipped presets)
is the dtype of every large matmul's inputs; accumulation, norms, softmax,
the router (logits at ``HIGHEST`` precision, softmax, top-k) and the loss
stay float32.

The second-level ``jax.named_scope``s (``LAYER_SCOPES``) and the third-level
ones inside them (``PIECE_SCOPES``) are what
``analysis.program.program_scopes`` puts a compiled program's operations down
to under the round's stages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from fedtpu.ops import packed_attention

EMBED, ATTENTION, ROUTER, EXPERT_DISPATCH, EXPERTS, LM_HEAD_LOSS = (
    "embed", "attention", "router", "expert_dispatch", "experts",
    "lm_head_loss")
# the hybrid stack's own (fedtpu.models.nemotron_h): a state-space mixer,
# the chunked scan alone inside it (innermost), the expert every token takes
SSM, SSM_SCAN, SHARED_EXPERT = "ssm", "ssm_scan", "shared_expert"
# the four-stream stack's own (fedtpu.models.xing4): the mixing of the
# residual streams around every sublayer, a plain gated MLP layer, and the
# projection that opens a multi-token-prediction module
HYPER_CONN, DENSE_MLP, MTP_PROJ = "hyper_conn", "dense_mlp", "mtp_proj"
# the delta-rule stack's own (fedtpu.models.kimi_linear): a KDA mixer, and
# the chunked recurrence alone inside it (innermost)
KDA, KDA_SCAN = "kda", "kda_scan"
LAYER_SCOPES = (EMBED, ATTENTION, ROUTER, EXPERT_DISPATCH, EXPERTS,
                LM_HEAD_LOSS, SSM, SSM_SCAN, SHARED_EXPERT, HYPER_CONN,
                DENSE_MLP, MTP_PROJ, KDA, KDA_SCAN)
# The third level (``parallel.round.PIECES``): the four parts of a state-space
# mixer around its scan, the attention core alone inside ``attention``, the
# Sinkhorn iterations alone inside ``hyper_conn``, the low-rank projections
# of latent attention (their norms and RoPE) beside the core, and the four
# parts of a KDA mixer around its scan.
(SSM_IN_PROJ, SSM_CONV, SSM_GATE_NORM, SSM_OUT_PROJ, ATTN_CORE, HC_SINKHORN,
 ATTN_LATENT, KDA_IN_PROJ, KDA_CONV, KDA_GATES, KDA_OUT_PROJ) = PIECE_SCOPES = (
    "ssm_in_proj", "ssm_conv", "ssm_gate_norm", "ssm_out_proj", "attn_core",
    "hc_sinkhorn", "attn_latent", "kda_in_proj", "kda_conv", "kda_gates",
    "kda_out_proj")
# An outer scope around a whole multi-token-prediction module, its layers'
# own scopes inside it (``parallel.round.MODULES``).
MTP = "mtp"
# A forward pass run again by hand inside a backward rule
# (``parallel.round.RECOMPUTE``): a direction, not a piece.
RECOMPUTE = "recompute"
# Rows of the sequence whose logits exist at one time in the loss.
LOSS_CHUNK = 512
INIT_STD = 0.02
# Rows and columns of a tile of the fused attention kernel, forward and both
# backward kernels. Chosen on the chip (PERF.md section 6, PR 26), forward +
# backward of one (4096, 16, 128) sequence: the library's default 128s take
# 17.0 ms (the XLA body 16.3), 256s 7.1, 512s 3.8, 1024s 3.7 with 134 MB
# more temporaries; no mixed shape beat 512s.
ATTENTION_BLOCK = 512


# Tiles of the three grouped expert kernels, ``(tm, tk, tn)`` = rows,
# contracted width, output width of a tile. Chosen on the chip (PERF.md
# section 6, PR 29) on the benchmark's shapes, 32,768 assignment rows in 64
# groups as its own corpus and router give them (the fullest group 2,065 to
# 2,976 rows, 23 to 32 groups under 128), 20 timed calls each, ms a call
# beside ``lax.ragged_dot`` on the same operands (its kernel alone takes
# 1.98-2.64 in the round; the calls timed here also hold its cast and its
# transposed copy of the weight):
#   gmm, [32768,2048].[64,2048,1024]: XLA 2.15; (256, 2048, 1024) 1.22,
#     (128, 2048, 1024) 1.23, (256, 2048, 512) 1.33, (512, 2048, 512) 1.50,
#     (256, 1024, 1024) 1.63, (256, 512, 512) 2.28, (128, 512, 512) 3.00.
#   gmm, [32768,1024].[64,1024,2048]: XLA 2.25; (128, 1024, 2048) 1.27,
#     (256, 1024, 2048) 1.30, (256, 1024, 1024) 1.36, (512, 1024, 1024)
#     1.57, (256, 512, 2048) 1.79, (128, 512, 512) 3.00.
#   gmm on the weight in place (transpose_rhs), to [32768,2048]: XLA 4.09;
#     (128 or 256, 1024, 2048) 1.23, (256, 1024, 1024) 1.28, (512, 1024,
#     2048) 1.48, (256, 512, 2048) 1.56; to [32768,1024]: XLA 3.61;
#     (256, 2048, 1024) 1.19, (128, 2048, 1024) 1.21, (512, 2048, 512) 1.51.
#   tgmm, to [64,2048,1024] and [64,1024,2048]: XLA 3.86; (256, 1024, 1024)
#     1.46 / 1.47, (256, 2048, 512) 1.51, (256, 512, 1024) 1.70, (512, 1024,
#     1024) 1.70, (1024, 1024, 512) 2.44; a float32 result +0.2 to +0.4.
# So: 256 rows (a tile that straddles a group's edge runs once a group: 512
# rows cost 1.2-1.3x, and 128 are no better with the weight held); the two
# gmm's take the contracted width whole and as much of the output width as
# one weight tile of 2048 x 1024 holds, so a group's weight is fetched once;
# tgmm takes 1024 x 1024 of the weight's gradient at a time. Wider tiles do
# not fit the kernel's 16 MB of the chip's own memory.
GROUPED_ROW_TILE = 256
GROUPED_WIDTH_TILE = 1024
GROUPED_WEIGHT_TILE = 2048 * 1024
# The same three kernels at the hybrid stack's widths (fedtpu.models.
# nemotron_h: 8 held experts of 2,688 x 1,856, neither a whole number of the
# tiles above). Chosen on the chip (PERF.md section 6, PR 33): one 8,192-row
# buffer in 8 groups as the cell's own corpus and router fill it (twelve
# layer-steps: 1,644 to 3,944 rows filled, groups of 43 to 1,272, the rest
# of the buffer past the last group), 20 timed calls a filling, mean ms a
# call beside ``lax.ragged_dot`` on the same operands. A tile that is no
# whole divisor of its width is cut by the kernel (1,856 = 640 + 640 + 576;
# 2,688 = 3 x 896); 1,344 and 928 are no whole lanes and no tile:
#   gmm, [8192,2688].[8,2688,1856], float32 out: XLA 2.17; (128, 896, 1856)
#     0.555, (128, 2688, 640) 0.558, (128, 2688, 512) 0.577, (256, 896,
#     1856) 0.596, (128, 2688, 768) 0.597, (256, 2688, 640) 0.598, (256,
#     2688, 768) 0.646, (256, 896, 1024) 0.661, (256, 2688, 896) 0.696,
#     (128, 1280, 640) 0.751; 512 rows do not fit with the width whole.
#   gmm, [8192,1856].[8,1856,2688], float32 out: XLA 1.68; (128 or 256,
#     1856, 896) 0.356, (128, 1856, 1408) 0.356, (256, 1856, 1024) 0.383,
#     (256, 640, 2688) 0.384, (256, 1856, 640) 0.409, (512, 1856, 896)
#     0.420, (128, 640, 2688) 0.503.
#   gmm on the weight in place (transpose_rhs), bf16 out, to [8192,1856]: XLA
#     2.07; (256, 2688, 640) 0.331, (128, 2688, 640) 0.342, (256, 896, 1856)
#     0.350, (256, 2688, 768) 0.382, (256, 2688, 896) 0.432, (128, 896, 1856)
#     0.479; to [8192,2688]: XLA 2.65; (128, 1856, 896) 0.401, (128, 1856,
#     1408) 0.406, (256, 1856, 896) 0.437, (128, 640, 2688) 0.471, (256,
#     1856, 1024) 0.473, (512, 1856, 896) 0.536.
#   tgmm, to [8,2688,1856]: XLA 2.25; (128, 896, 1856) 0.442, (128, 384,
#     1856) 0.478, (256, 896, 1856) 0.479, (128, 2688, 384) 0.496, (128, 896,
#     1024) 0.517, (128, 896, 640) 0.542, (256, 1024, 1024) 0.604, (512, 896,
#     1856) 0.574; to [8,1856,2688]: XLA 2.82; (128, 640, 2688) 0.407, (128
#     or 256, 1856, 896) 0.408, (128, 1024, 1408) 0.442, (128, 640, 896)
#     0.468, (256, 1024, 1024) 0.503, (512, 640, 896) 0.550.
#   With the 1,856 padded to 1,920 = 15 x 128 (zero columns, exact): 0.323 /
#     0.318 / 0.301 / 0.303 / 0.356 / 0.358 at the best tile of each, 1.96
#     for the six against 2.51: not taken, a padded copy of both weights,
#     of the activations and a cut of both gradients for 0.55 ms.
# So: 128 rows (a group here is one to three tiles of 256, where OLMoE's are
# eight to twelve, and a tile that straddles a group's edge runs once a
# group); the two gmm's take the contracted width whole and a third of the
# output width, so a group's weight is fetched once; tgmm takes a third of
# 2,688 by the whole of 1,856. The formula above gives (256, 2688, 768),
# (256, 1856, 1024) and (256, 1024, 1024) here: 2.99 for the six against
# 2.51. Neither kernel visits a tile past the last group: the XLA body's
# 1.7-2.8 ms are mostly the buffer's empty rows.
_MEASURED_TILES = {
    ("forward", 2688, 1856): (128, 2688, 640),
    ("forward", 1856, 2688): (128, 1856, 896),
    ("input_gradient", 2688, 1856): (128, 2688, 640),
    ("input_gradient", 1856, 2688): (128, 1856, 896),
    ("weight_gradient", 2688, 1856): (128, 896, 1856),
    ("weight_gradient", 1856, 2688): (128, 1856, 896),
}


def _grouped_tiles(kernel, k, n):
    """``(tm, tk, tn)`` of one of the three grouped kernels for a contracted
    width ``k`` and an output width ``n`` (of ``tgmm``: the weight's two)."""
    measured = _MEASURED_TILES.get((kernel, k, n))
    if measured:
        return measured
    if kernel == "weight_gradient":
        return (GROUPED_ROW_TILE, min(k, GROUPED_WIDTH_TILE),
                min(n, GROUPED_WIDTH_TILE))
    return GROUPED_ROW_TILE, k, min(n, GROUPED_WEIGHT_TILE // k)


def olmoe_init(key: jax.Array, cfg, param_dtype=jnp.float32):
    """N(0, 0.02) weights (the family's initializer range), unit norm
    gains; layer leaves carry a leading ``num_hidden_layers`` axis."""
    h, e, i, v, n = (cfg.hidden_size, cfg.num_experts, cfg.intermediate_size,
                     cfg.vocab_size, cfg.num_hidden_layers)
    keys = iter(jax.random.split(key, 10))

    def normal(shape):
        return INIT_STD * jax.random.normal(next(keys), shape, param_dtype)

    ones = lambda *shape: jnp.ones(shape, param_dtype)
    return {
        "embed": normal((v, h)),
        "layers": {
            "attn_norm": ones(n, h), "q": normal((n, h, h)),
            "k": normal((n, h, h)), "v": normal((n, h, h)),
            "o": normal((n, h, h)), "q_norm": ones(n, h),
            "k_norm": ones(n, h), "mlp_norm": ones(n, h),
            "router": normal((n, h, e)), "gate": normal((n, e, h, i)),
            "up": normal((n, e, h, i)), "down": normal((n, e, i, h)),
        },
        "final_norm": ones(h),
        "head": normal((h, v)),
    }


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def segment_positions(segs):
    """Position of each token within its segment: 0 at every token whose
    segment id differs from the one before it."""
    idx = jnp.arange(segs.shape[0], dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])
    return idx - lax.cummax(jnp.where(starts, idx, 0))


def _rope(x, pos, theta, inv=None):
    """Rotate-half RoPE over all of the last axis; x ``(T, heads, d)``.
    ``inv (d / 2,)``: the frequencies, where a model scales its own."""
    d = x.shape[-1]
    if inv is None:
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def route(x, router_w, top_k: int, norm_topk_prob: bool):
    """``(gates (T, k) float32, experts (T, k) int32)``: softmax over every
    expert's logit in float32, the top k of it, unrenormalised unless the
    config says otherwise."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    gates, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    return gates, experts.astype(jnp.int32)


def padded_head_width(q, v) -> int:
    """The head width the tiled kernel would run ``q (T, heads, dq)`` and
    ``v (T, heads, dv)`` at: the wider of the two, up to whole lane tiles.
    The kernel takes one width for q, k and v; zero columns of q and k add
    nothing to a score and zero columns of v give zero columns of the
    context, which are cut, so the padded form is exact."""
    return -(-max(q.shape[-1], v.shape[-1]) // 128) * 128


def fused_attention_applies(q, k, v) -> bool:
    """Whether the tiled kernel exists for these ``(T, heads, d)`` operands
    where the program is being built: a TPU, lane-wide heads, whole blocks
    and one head width for q, k and v (``attention_core`` pads a head whose
    query-key and value widths differ to one before it asks).

    The platform read is the PROCESS's default backend, not the one a
    program is lowered for: a compile for a described TPU from a CPU host
    gets the XLA body (``tests/test_aot_tpu_compile.py`` steers this rule
    for that reason), and a CPU mesh on a TPU host at these widths would
    get a kernel it cannot lower."""
    t, _, d = q.shape
    return (jax.default_backend() == "tpu" and q.shape == k.shape == v.shape
            and d % 128 == 0 and t % ATTENTION_BLOCK == 0)


def _xla_attention(q, k, v, segs, scale=None):
    t, _, d = q.shape
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / (d ** 0.5) if scale is None else scores * scale
    idx = jnp.arange(t)
    # causal, and within one segment; padding (segment 0) sees padding,
    # which keeps its rows finite and is masked out of the loss
    allowed = (idx[:, None] >= idx[None, :]) & (segs[:, None] == segs[None, :])
    probs = jax.nn.softmax(jnp.where(allowed[None], scores, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _fused_attention(q, k, v, segs, scale=None):
    # the kernels' layout is (heads, T, d); their mask is the XLA body's:
    # causal, and equal segment ids (padding's 0 among them)
    heads_first = lambda a: a.transpose(1, 0, 2)
    ctx = packed_attention.attention(
        heads_first(q), heads_first(k), heads_first(v), segs,
        q.shape[-1] ** -0.5 if scale is None else scale, ATTENTION_BLOCK)
    return ctx.transpose(1, 0, 2).astype(jnp.float32)


def attention_blocks(segs, fused: bool, layers: int) -> dict:
    """A sequence's two block counters: the (query block, key block) pairs
    the fused body's forward kernel runs on the row ``segs (T,)``, from the
    table it reads, and the pairs on or under the diagonal, a head's worth
    for each of ``layers`` attention layers; both 0 where the XLA body ran
    (it has no blocks)."""
    if not fused:
        return {"attention_blocks_computed": jnp.float32(0.0),
                "attention_blocks_causal": jnp.float32(0.0)}
    kept = packed_attention.pairs_kept(segs, ATTENTION_BLOCK)
    blocks = kept.shape[0]
    return {"attention_blocks_computed":
            layers * kept.sum().astype(jnp.float32),
            "attention_blocks_causal":
            jnp.float32(layers * blocks * (blocks + 1) // 2)}


def attention_core(q, k, v, segs, compute_dtype, scale=None):
    """``ctx (T, heads, dv)`` float32: the attention of one packed sequence
    after RoPE and before the output projection, ``q``, ``k`` ``(T, heads,
    dq)`` and ``v (T, heads, dv)`` float32 and cast to ``compute_dtype`` for
    both matmuls; the scores are scaled by ``scale`` (``dq ** -0.5`` where
    none is given). A head whose two widths differ (latent attention: 192
    beside 128) reaches the tiled kernel padded with zeros to one width
    (``padded_head_width``) and its context is cut back: exact, at the
    padded width's cost. The XLA body takes the widths as they are."""
    q, k, v = (a.astype(compute_dtype) for a in (q, k, v))
    if scale is None and q.shape == v.shape:
        padded = q, k, v
    else:
        if scale is None:
            scale = q.shape[-1] ** -0.5
        wide = padded_head_width(q, v)
        padded = tuple(jnp.pad(a, ((0, 0), (0, 0), (0, wide - a.shape[-1])))
                       for a in (q, k, v))
    with jax.named_scope(ATTN_CORE):
        if not fused_attention_applies(*padded):
            return _xla_attention(q, k, v, segs, scale)
        ctx = _fused_attention(*padded, segs, scale)
        return ctx if padded[2] is v else ctx[..., :v.shape[-1]]


def grouped_matmul_applies(xs, w) -> bool:
    """Whether the tiled kernels exist for ``xs (rows, K)`` and ``w (groups,
    K, N)`` where the program is being built: a TPU (the PROCESS's backend,
    as ``fused_attention_applies`` reads it), the bf16 operands the tiles
    were measured on (the kernel multiplies float32 operands in float32,
    several MXU passes where the XLA body takes one), whole row tiles, and
    a pair of widths that has tiles from a sweep on the chip: each width
    whole width tiles and, as the contracted width of a kernel, leaving a
    width tile's room in one weight tile (1024 or 2048), or the pair in
    ``_MEASURED_TILES`` (2688 and 1856). Any other width (1408, 4096) runs
    ``lax.ragged_dot`` until it has a sweep of its own."""
    (rows, k), n = xs.shape, w.shape[2]
    return (jax.default_backend() == "tpu"
            and xs.dtype == w.dtype == jnp.bfloat16
            and rows % GROUPED_ROW_TILE == 0
            and (("forward", k, n) in _MEASURED_TILES
                 or all(width % GROUPED_WIDTH_TILE == 0
                        and width * GROUPED_WIDTH_TILE <= GROUPED_WEIGHT_TILE
                        for width in (k, n))))


def _xla_grouped_matmul(xs, w, sizes):
    return lax.ragged_dot(xs, w, group_sizes=sizes,
                          preferred_element_type=jnp.float32)


def _rows_of_groups(out, sizes):
    # the kernel visits no tile past the last group: what it left there is
    # not zero, as the definition's is, until it is made so
    rows = lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < sizes.sum(), out, 0)


@jax.custom_vjp
def _pallas_grouped_matmul(xs, w, sizes):
    """The library's grouped matmul (``megablox.gmm``) under a rule of its
    own, reverse mode only: the input's gradient is the same kernel reading
    the weight in place (``transpose_rhs``: no transposed copy of it
    exists), the weight's is ``tgmm``. The cotangent enters both in the
    operands' dtype (what the MXU made of the float32 one autodiff handed
    the XLA body), sums are float32 over the whole contracted width, and
    each gradient is rounded once to its primal's dtype."""
    k, n = w.shape[1:]
    return _rows_of_groups(gmm(
        xs, w, sizes, jnp.float32, _grouped_tiles("forward", k, n)), sizes)


def _pallas_grouped_matmul_fwd(xs, w, sizes):
    return _pallas_grouped_matmul(xs, w, sizes), (xs, w, sizes)


def _pallas_grouped_matmul_bwd(residuals, g):
    xs, w, sizes = residuals
    k, n = w.shape[1:]
    g = g.astype(xs.dtype)
    dxs = _rows_of_groups(gmm(
        g, w, sizes, xs.dtype, _grouped_tiles("input_gradient", n, k),
        transpose_rhs=True), sizes)
    # tgmm takes the activations contracted-axis last and swaps them back
    # itself: the two transposes meet under jit and no copy is made
    dw = tgmm(xs.swapaxes(0, 1), g, sizes, w.dtype,
              _grouped_tiles("weight_gradient", k, n))
    return dxs, dw, None


_pallas_grouped_matmul.defvjp(_pallas_grouped_matmul_fwd,
                              _pallas_grouped_matmul_bwd)


def grouped_matmul(xs, w, sizes):
    """``out (rows, N)`` float32: rows ``sizes[:g].sum()`` to
    ``sizes[:g + 1].sum()`` of ``xs (rows, K)`` times ``w[g] (K, N)``, for
    every group; rows past the last group are zero. ``lax.ragged_dot`` is
    the definition and the XLA body."""
    body = (_pallas_grouped_matmul if grouped_matmul_applies(xs, w)
            else _xla_grouped_matmul)
    return body(xs, w, sizes)


def sorted_assignments(groups, n_groups: int):
    """``(order, sizes)`` of the assignments ``groups (A,)`` int32, each the
    group (expert) one row goes to: ``order`` lists the assignments group by
    group, earlier ones first within a group, and ``sizes (n_groups,)`` are
    the groups' loads. The dispatch of every expert layer starts here."""
    order = jnp.argsort(groups, stable=True)
    sizes = jnp.bincount(groups, length=n_groups).astype(jnp.int32)
    return order, sizes


def gather_rows(x, order, per_token: int):
    """The tokens' rows in the order of their assignments: assignment ``a``
    of the token-major list belongs to token ``a // per_token``."""
    return jnp.take(x, order // per_token, axis=0)


def _block(cfg, compute_dtype, h, layer, segs, pos):
    """One decoder layer on one packed sequence ``h (T, H)``; returns the
    new ``h`` and the tokens each expert was given (padding left out)."""
    t, hid = h.shape
    heads = cfg.num_attention_heads
    hd = hid // heads
    eps = cfg.rms_norm_eps
    cast = lambda a: a.astype(compute_dtype)
    mm = functools.partial(jnp.dot, preferred_element_type=jnp.float32)

    with jax.named_scope(ATTENTION):
        x = cast(rms_norm(h, layer["attn_norm"], eps))
        q = rms_norm(mm(x, cast(layer["q"])), layer["q_norm"], eps)
        k = rms_norm(mm(x, cast(layer["k"])), layer["k_norm"], eps)
        v = mm(x, cast(layer["v"])).reshape(t, heads, hd)
        q = _rope(q.reshape(t, heads, hd), pos, cfg.rope_theta)
        k = _rope(k.reshape(t, heads, hd), pos, cfg.rope_theta)
        ctx = attention_core(q, k, v, segs, compute_dtype)
        h = h + mm(cast(ctx.reshape(t, hid)), cast(layer["o"]))

    top_k, n_exp = cfg.num_experts_per_tok, cfg.num_experts
    with jax.named_scope(ROUTER):
        x = rms_norm(h, layer["mlp_norm"], eps)
        gates, experts = route(x, layer["router"], top_k, cfg.norm_topk_prob)
    with jax.named_scope(EXPERT_DISPATCH):
        # assignments sorted by expert: row a of the sorted list is token
        # order[a] // k, and the groups' sizes are the experts' loads
        flat = experts.reshape(-1)
        order, sizes = sorted_assignments(flat, n_exp)
        xs = gather_rows(cast(x), order, top_k)
        real = (segs > 0).astype(jnp.int32)
        load = jnp.zeros((n_exp,), jnp.int32).at[flat].add(
            jnp.repeat(real, top_k))
    with jax.named_scope(EXPERTS):
        act = (jax.nn.silu(grouped_matmul(xs, cast(layer["gate"]), sizes))
               * grouped_matmul(xs, cast(layer["up"]), sizes))
        ys = grouped_matmul(cast(act), cast(layer["down"]), sizes)
    with jax.named_scope(EXPERT_DISPATCH):
        back = jnp.take(ys, jnp.argsort(order), axis=0, unique_indices=True)
        h = h + (back.reshape(t, top_k, hid) * gates[..., None]).sum(axis=1)
    return h, load


def next_token_targets(tokens, segs):
    """``(labels (T,), valid (T,) float32)``: the next token where it belongs
    to the same document; padding and each document's last token are out."""
    labels = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    nxt = jnp.concatenate([segs[1:], jnp.zeros((1,), segs.dtype)])
    return labels, ((segs > 0) & (nxt == segs)).astype(jnp.float32)


def _loss_chunks(h, labels, valid):
    """The head's inputs cut into ``LOSS_CHUNK`` rows, or left as one chunk
    where the sequence is no multiple of it."""
    t = h.shape[0]
    chunk = LOSS_CHUNK if t % LOSS_CHUNK == 0 else t
    return (h.reshape(-1, chunk, h.shape[1]), labels.reshape(-1, chunk),
            valid.reshape(-1, chunk))


def _chunk_loss(x, w, yc, vc):
    """One chunk's float32 ``(logits, log-sum-exp, summed loss, correct)``
    from ``x (chunk, H)`` and ``w (H, V)`` in the compute dtype."""
    logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, yc[:, None], axis=-1)[:, 0]
    hit = (jnp.argmax(logits, axis=-1) == yc).astype(jnp.float32)
    return logits, lse, ((lse - picked) * vc).sum(), (hit * vc).sum()


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _head_loss(h, head, labels, valid, compute_dtype):
    """``(summed loss, correct)`` over a sequence, a chunk of rows at a
    time. Called plainly (held-out evaluation) this is the forward pass
    alone. Differentiated, its own rule runs instead (``_head_loss_fwd``),
    reverse mode only: ``jax.jvp``, ``jacfwd`` and ``hessian`` through the
    head raise, and nothing in fedtpu uses them. ``labels`` and ``valid``
    are data (functions of the integer row): their cotangents are zero."""
    w = head.astype(compute_dtype)

    def one(carry, xs):
        hc, yc, vc = xs
        _, _, loss, correct = _chunk_loss(hc.astype(compute_dtype), w, yc, vc)
        return (carry[0] + loss, carry[1] + correct), None

    zero = jnp.float32(0.0)
    return lax.scan(one, (zero, zero), _loss_chunks(h, labels, valid))[0]


def _head_loss_fwd(h, head, labels, valid, compute_dtype):
    """The loss is the model's last operation and its cotangent one scalar,
    so each chunk's logits give, while they exist, the loss AND its gradient
    for a unit cotangent: ``dlogits = (softmax - onehot) * valid``,
    ``dh = dlogits w^T``, ``dw += h^T dlogits``. Three matmuls over the
    vocabulary a chunk and no recomputation; the backward rule only scales
    ``(dh, dw)``. ``dlogits`` enters its two matmuls in the compute dtype
    (what the MXU made of the float32 one autodiff handed it); ``dw`` is
    summed over the chunks in the compute dtype, as autodiff summed it,
    each chunk's float32 product added in float32 and rounded once."""
    w = head.astype(compute_dtype)

    def one(carry, xs):
        hc, yc, vc = xs
        loss, correct, dw = carry
        x = hc.astype(compute_dtype)
        logits, lse, chunk_loss, chunk_correct = _chunk_loss(x, w, yc, vc)
        onehot = yc[:, None] == jnp.arange(logits.shape[1])[None, :]
        dlogits = ((jnp.exp(logits - lse[:, None]) - onehot)
                   * vc[:, None]).astype(compute_dtype)
        dh = lax.dot_general(dlogits, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        dw = (dw.astype(jnp.float32) + lax.dot_general(
            x, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)).astype(dw.dtype)
        return (loss + chunk_loss, correct + chunk_correct, dw), dh

    zero = jnp.float32(0.0)
    (loss, correct, dw), dh = lax.scan(
        one, (zero, zero, jnp.zeros_like(w)), _loss_chunks(h, labels, valid))
    return (loss, correct), (dh.reshape(h.shape).astype(h.dtype), dw, head)


def _head_loss_bwd(compute_dtype, residuals, cotangents):
    dh, dw, head = residuals    # head: for its dtype, the parameters'
    g = cotangents[0]           # ``correct`` is a count: no gradient
    return ((g * dh).astype(dh.dtype),
            (g * dw.astype(jnp.float32)).astype(head.dtype), None, None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def olmoe_sequence_stats(params, row, cfg, compute_dtype=jnp.float32):
    """One packed row ``(2, T)`` through the model: the sufficient statistics
    of the next-token task and the expert counters, all sums over tokens:
    ``loss_sum``, ``correct``, ``count`` (tokens in the loss), ``tokens``
    (of any document), ``padding`` (tokens of segment 0), ``expert_load (E,)`` (real tokens given to each
    expert, summed over layers), ``fused_attention`` (positions whose
    attention ran in the fused body: T or 0), ``grouped_experts`` (positions
    whose expert matmuls ran in the tiled kernels: T or 0),
    ``attention_blocks_computed`` and ``attention_blocks_causal``
    (``attention_blocks``)."""
    tokens, segs = row[0], row[1]
    pos = segment_positions(segs)
    # the operands every layer's attention core is given: static shapes, so
    # the rule between its bodies is read once, here
    t, heads = tokens.shape[0], cfg.num_attention_heads
    core = jax.ShapeDtypeStruct((t, heads, cfg.hidden_size // heads),
                                compute_dtype)
    fused = fused_attention_applies(core, core, core)
    rows, wide, narrow = (t * cfg.num_experts_per_tok, cfg.hidden_size,
                          cfg.intermediate_size)
    grouped = all(grouped_matmul_applies(
        jax.ShapeDtypeStruct((rows, k), compute_dtype),
        jax.ShapeDtypeStruct((cfg.num_experts, k, n), compute_dtype))
        for k, n in ((wide, narrow), (narrow, wide)))
    with jax.named_scope(EMBED):
        h = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    def layer_step(h, layer):
        return _block(cfg, compute_dtype, h, layer, segs, pos)

    h, loads = lax.scan(layer_step, h, params["layers"])
    with jax.named_scope(LM_HEAD_LOSS):
        labels, valid = next_token_targets(tokens, segs)
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        loss, correct = _head_loss(h, params["head"], labels, valid,
                                   compute_dtype)
    return {"loss_sum": loss, "correct": correct, "count": valid.sum(),
            "tokens": (segs > 0).sum().astype(jnp.float32),
            "padding": (segs == 0).sum().astype(jnp.float32),
            "expert_load": loads.sum(axis=0),
            "fused_attention": jnp.float32(t if fused else 0),
            "grouped_experts": jnp.float32(t if grouped else 0),
            **attention_blocks(segs, fused, cfg.num_hidden_layers)}


def olmoe_stats(params, x, mask, cfg, compute_dtype=jnp.float32):
    """``olmoe_sequence_stats`` summed over the rows ``x (N, 2, T)`` whose
    ``mask`` is 1, one row at a time (a padded row counts for nothing)."""
    def one(row_and_mask):
        row, m = row_and_mask
        # a padded row is all segment 0: nothing of it is counted
        stats = olmoe_sequence_stats(params, row * m.astype(row.dtype), cfg,
                                     compute_dtype)
        return {**stats, **{k: stats[k] * m for k in (
            "padding", "fused_attention", "grouped_experts",
            "attention_blocks_computed", "attention_blocks_causal")}}

    if x.shape[0] == 1:
        return one((x[0], mask[0]))
    stats = lax.map(one, (x, mask))
    return jax.tree.map(lambda a: a.sum(axis=0), stats)
