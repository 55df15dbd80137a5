"""OLMoE (fedtpu.models.olmoe) against the plain reference
(perfbench/reference_lm.py: float32 at 'highest', dense experts, whole
logits), on seeded weights at a tiny size."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from fedtpu.config import ModelConfig
from fedtpu.models import layers, olmoe
from fedtpu.models.registry import build_model
from fedtpu.ops import grouped_matmul as grouped
from fedtpu.ops import lm_head
from fedtpu.ops import packed_attention as attn
from perfbench import reference_lm

TINY = ModelConfig(kind="olmoe", hidden_size=32, num_attention_heads=4,
                   num_hidden_layers=1, num_experts=8, num_experts_per_tok=2,
                   intermediate_size=16, vocab_size=64)
T = 32
# The size at which the fused attention body exists (lane-wide heads, whole
# blocks), two of its blocks long; the rest as tiny as TINY.
FUSED_T, FUSED_HEADS, FUSED_D = 2 * attn.ATTENTION_BLOCK, 2, 128
FUSED = ModelConfig(kind="olmoe", hidden_size=FUSED_HEADS * FUSED_D,
                    num_attention_heads=FUSED_HEADS, num_hidden_layers=1,
                    num_experts=8, num_experts_per_tok=2,
                    intermediate_size=16, vocab_size=64)
# The size at which the grouped expert kernels exist (lane-wide widths, whole
# row tiles): top-2 of 8 experts over four row tiles of assignments.
GROUPED_T = 2 * grouped.GROUPED_ROW_TILE
GROUPED = ModelConfig(kind="olmoe", hidden_size=128, num_attention_heads=4,
                      num_hidden_layers=1, num_experts=8,
                      num_experts_per_tok=2, intermediate_size=128,
                      vocab_size=64)
REF_CFG = {k: getattr(TINY, k) for k in
           ("num_attention_heads", "num_experts_per_tok", "rope_theta",
            "rms_norm_eps", "norm_topk_prob")}


@pytest.fixture(autouse=True)
def _small_loss_chunks(monkeypatch):
    # four chunks of the 32-token rows, so the chunked loss is what runs
    monkeypatch.setattr(lm_head, "LOSS_CHUNK", 8)


def _row(seed, docs=(12, 14), t=T, vocab=TINY.vocab_size):
    """A packed row: documents of the given lengths, then padding."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(1, vocab, t).astype(np.int32)
    segs = np.zeros(t, np.int32)
    at = 0
    for i, n in enumerate(docs):
        segs[at:at + n] = i + 1
        at += n
    tokens[at:] = 0
    return np.stack([tokens, segs])


def _params(cfg=TINY, seed=0):
    init, _ = build_model(cfg)
    p = init(jax.random.key(seed))
    # norm gains off 1, so a dropped or misplaced gain would show
    k = iter(jax.random.split(jax.random.key(seed + 1), 8))
    bump = lambda a: a + 0.1 * jax.random.normal(next(k), a.shape)
    p["layers"] = {n: (bump(a) if n.endswith("norm") else a)
                   for n, a in p["layers"].items()}
    p["final_norm"] = bump(p["final_norm"])
    # larger weights than N(0, 0.02): the router must prefer some experts
    return jax.tree.map(lambda a: a * 4.0 if a.ndim > 1 else a, p)


def _sys_loss(cfg, dtype):
    def f(p, row):
        s = olmoe.sequence_stats(p, row, cfg, dtype)
        return s["loss_sum"] / jnp.maximum(s["count"], 1.0), s
    return f


def _ref_loss(p, row):
    with jax.default_matmul_precision("highest"):
        return reference_lm.mean_loss(p, row, REF_CFG)


def _gap(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# float32 compute agrees with the float32 reference to rounding (measured:
# 5e-7 on the loss, 8e-7 on gradients over 20 seeds), so 1e-5. bfloat16
# inputs to every large matmul carry 2**-9 relative error each: over 20
# seeds the loss moved by 1e-4 to 1.1e-2, and the gradients by 1.7% of the
# largest gradient entry where no router choice changed, up to 35% where a
# near-tie between two experts fell the other way (the router is float32
# but its input passed bf16 attention). This seed has no such flip and sits
# at 8e-5 / 3.6e-2 (largest gradient entry 1.1): 3e-2 / 6e-2 hold bf16 and
# fail a path that drops a term or computes in 8 bits.
#
# The grouped cases run the Pallas body of the expert matmuls (the library's
# kernels, interpreted) at a size it has tiles for, 512 tokens, jitted. In
# float32 the reference holds it as it holds the XLA body (over three seeds
# 4.8e-7 on the loss, 3.1e-7 to 4.5e-7 on gradient entries up to 0.42). In
# bfloat16 at that length some router choice always flips against the
# reference (either body: 0.11 to 0.21 on the gradients), so the XLA body at
# the same size is what is held to: nothing before the experts differs, the
# forward kernels give the XLA body's sums (0 to 1.9e-6 on the loss) and the
# gradients differ by the cotangent's rounding to bf16, 1.8e-3 to 1.9e-3 on
# entries up to 0.42 (the experts' own up to 0.014): 1e-4 / 6e-3 hold that
# and fail a dropped tile or group, which moves entries by their own size.
@pytest.mark.parametrize("experts", ["xla", "grouped"])
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 3e-2, 6e-2)])
def test_loss_and_gradients_match_the_reference(dtype, loss_tol, grad_tol,
                                                experts, request):
    cfg, t, docs = TINY, T, (12, 14)
    if experts == "grouped":
        cfg, t = GROUPED, GROUPED_T
        docs = (3 * t // 8, t // 2)
    p, row = _params(cfg), jnp.asarray(_row(3, docs, t))
    # traced when first called: before the fixture with the XLA body of the
    # experts, after it with the Pallas body
    system = lambda: jax.jit(jax.value_and_grad(_sys_loss(cfg, dtype), has_aux=True))
    against_xla = experts == "grouped" and dtype == jnp.bfloat16
    if against_xla:
        reference, (loss_tol, grad_tol) = system(), (1e-4, 6e-3)
    else:
        reference = jax.jit(jax.value_and_grad(_ref_loss, has_aux=True))
    (ref, ref_aux), rg = reference(p, row)
    if experts == "grouped":
        request.getfixturevalue("grouped_on_the_cpu")
    with_gradients = system()
    (loss, stats), g = with_gradients(p, row)
    assert float(stats["count"]) == sum(docs) - 2
    if not against_xla:
        assert float(ref_aux[1]) == sum(docs) - 2
    assert float(stats["padding"]) == t - sum(docs)
    assert float(stats["grouped_experts"]) == (t if experts == "grouped" else 0)
    assert abs(float(loss) - float(ref)) <= loss_tol
    assert _gap(g, rg) <= grad_tol
    assert float(jnp.max(jnp.abs(rg["layers"]["gate"]))) > 1e-4   # experts do train


def test_top_k_sets_are_the_references_in_float32():
    p, row = _params(), jnp.asarray(_row(5))
    x = jax.random.normal(jax.random.key(9), (T, TINY.hidden_size))
    router = p["layers"]["router"][0]
    gates, experts = olmoe.route(x, router, 2, False)
    with jax.default_matmul_precision("highest"):
        dense = reference_lm.gate_weights(x, router, 2)
    mine = jnp.zeros_like(dense).at[jnp.arange(T)[:, None], experts].set(gates)
    assert np.array_equal(np.asarray(mine > 0), np.asarray(dense > 0))
    np.testing.assert_allclose(mine, dense, atol=1e-7)
    assert float(gates.sum(-1).max()) < 1.0         # not renormalised


# The grouped case is the same skew through the Pallas body at its own size:
# one group holds half of the assignments (every token's first choice) and
# spans two row tiles whole, and experts nobody chose are empty groups.
@pytest.mark.parametrize("experts", ["xla", "grouped"])
def test_dropless_under_a_skew_over_four_times_the_mean(experts, request):
    base, t = (GROUPED, GROUPED_T) if experts == "grouped" else (TINY, T)
    cfg = dataclasses.replace(base, num_experts=16)
    p = _params(cfg)
    # every token prefers expert 3: hidden states made positive (positive
    # embeddings, no attention output, unit gain) meet a positive column
    p["embed"] = jnp.abs(p["embed"])
    p["layers"]["o"] = jnp.zeros_like(p["layers"]["o"])
    p["layers"]["mlp_norm"] = jnp.ones_like(p["layers"]["mlp_norm"])
    p["layers"]["router"] = p["layers"]["router"].at[0, :, 3].set(0.5)
    row = jnp.asarray(_row(7, docs=(t,), t=t))
    reference = jax.jit(jax.value_and_grad(_ref_loss, has_aux=True))
    (ref, _), rg = reference(p, row)
    if experts == "grouped":
        request.getfixturevalue("grouped_on_the_cpu")
    system = jax.jit(jax.value_and_grad(_sys_loss(cfg, jnp.float32), has_aux=True))
    (loss, stats), g = system(p, row)
    load = np.asarray(stats["expert_load"])
    routed = int(load.sum())
    assert load[3] == t and load[3] > 4 * load.mean()   # 8x the mean here
    assert routed == cfg.num_experts_per_tok * t    # every assignment computed
    dropped = cfg.num_experts_per_tok * int(t - stats["padding"]) - routed
    assert dropped == 0
    assert abs(float(loss) - float(ref)) <= 1e-5 and _gap(g, rg) <= 1e-5


def _summed_loss_and_gradients(cfg):
    def total(p, rows):
        s = [olmoe.sequence_stats(p, r, cfg) for r in rows]
        return sum(x["loss_sum"] for x in s), s
    return jax.value_and_grad(total, has_aux=True)


@pytest.fixture
def fused_on_the_cpu(monkeypatch):
    """The whole model through the fused attention body: the rule between
    the bodies is steered to it and the kernel interpreted (always under
    jit: the interpreter is not for eager use)."""
    monkeypatch.setattr(attn, "fused_attention_applies", lambda q, k, v: True)
    with pltpu.force_tpu_interpret_mode():
        yield


# The XLA case is the tiny model, eager, its gradients within 1e-5 as the
# file's others are. The fused case is two of the kernel's blocks long, its
# documents' edge 40 tokens before the blocks': the segment mask inside a
# block, the online softmax over two key blocks and the causal skip are what
# runs. There, gradients of 1,024 tokens summed in another order differ by
# 1.9e-4 to 3.1e-4 on entries up to 287 over three seeds (the XLA body at
# that size: 1.8e-4 to 2.3e-4), hence 1e-5 of the largest entry; one token
# seen across the edge moves entries by thousandths of their size.
@pytest.mark.parametrize("body", ["xla", "fused"])
def test_two_packed_documents_give_what_the_two_alone_give(body, request):
    if body == "fused":
        request.getfixturevalue("fused_on_the_cpu")
        cfg, t, (a, b) = FUSED, FUSED_T, (FUSED_T // 2 - 40, FUSED_T // 2 + 10)
        f = jax.jit(_summed_loss_and_gradients(cfg))
    else:
        cfg, t, (a, b) = TINY, T, (12, 14)
        f = _summed_loss_and_gradients(cfg)
    p = _params(cfg)
    both = _row(11, docs=(a, b), t=t)
    alone = []
    for lo, hi in ((0, a), (a, a + b)):
        r = np.zeros_like(both)
        r[:, :hi - lo] = both[:, lo:hi]
        r[1, :hi - lo] = 1
        alone.append(jnp.asarray(r))
    (packed, s), g = f(p, [jnp.asarray(both)])
    (apart, sa), ga = f(p, alone)
    assert float(s[0]["fused_attention"]) == (t if body == "fused" else 0)
    assert abs(float(packed) - float(apart)) <= 1e-4 * abs(float(apart))
    largest = max(float(jnp.max(jnp.abs(x))) for x in jax.tree.leaves(ga))
    assert _gap(g, ga) <= (1e-5 * largest if body == "fused" else 1e-5)
    assert [float(x["count"]) for x in sa] == [a - 1.0, b - 1.0]


@pytest.mark.parametrize("experts", ["xla", "grouped"])
def test_depth_two_scanned_is_two_blocks_by_hand(experts, request):
    base, t = (GROUPED, GROUPED_T) if experts == "grouped" else (TINY, T)
    cfg = dataclasses.replace(base, num_hidden_layers=2)
    p, row = _params(cfg), jnp.asarray(_row(13, (3 * t // 8, t // 2), t))
    reference = jax.jit(lambda p, row: reference_lm.sequence_loss(p, row, REF_CFG))
    with jax.default_matmul_precision("highest"):
        ref, _ = reference(p, row)
    if experts == "grouped":
        request.getfixturevalue("grouped_on_the_cpu")
    scanned = jax.jit(lambda p, row: olmoe.sequence_stats(p, row, cfg))
    got = scanned(p, row)

    @jax.jit
    def by_hand(p, row):
        tokens, segs = row
        pos = layers.segment_positions(segs)
        h = p["embed"][tokens]
        for i in range(2):
            h, _ = olmoe._block(cfg, jnp.float32, h,
                                jax.tree.map(lambda a: a[i], p["layers"]),
                                segs, pos)
        labels, valid = lm_head.next_token_targets(tokens, segs)
        return lm_head._head_loss(
            layers.rms_norm(h, p["final_norm"], cfg.rms_norm_eps), p["head"],
            labels, valid, jnp.float32)[0]

    want = by_hand(p, row)
    # sums over the sequence: 32 tokens' to 1e-5 and 1e-4, 512 in proportion
    assert abs(float(got["loss_sum"]) - float(want)) <= 1e-5 * t / T
    assert abs(float(got["loss_sum"]) - float(ref)) <= 1e-4 * t / T


# The head's own differentiation rule (lm_head._head_loss) against plain
# autodiff of the head and loss written here whole: no chunks, no checkpoint,
# no rule.
def _plain_head_loss(h, head, labels, valid, dtype):
    logits = jnp.dot(h.astype(dtype), head.astype(dtype),
                     preferred_element_type=jnp.float32)
    picked = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                 labels[:, None], axis=-1)[:, 0]
    hit = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    return -(picked * valid).sum(), (hit * valid).sum()


def _head_inputs(t, seed=21):
    """Hidden states and a head that give gradient entries of order 1, and
    the targets of a packed row: two documents (the last token of each out
    of the loss), then padding."""
    kh, kw = jax.random.split(jax.random.key(seed))
    h = 2.0 * jax.random.normal(kh, (t, TINY.hidden_size))
    head = 0.5 * jax.random.normal(kw, (TINY.hidden_size, TINY.vocab_size))
    row = _row(seed, docs=(t // 3, t // 2), t=t)
    labels, valid = lm_head.next_token_targets(jnp.asarray(row[0]),
                                             jnp.asarray(row[1]))
    assert float(valid.sum()) == t // 3 + t // 2 - 2 < t - 4
    return h, head, labels, valid


# float32: the same sums in another order (over 20 seeds, either length: 0 to
# 2.9e-6 on a mean loss of 12-15, 1e-7 to 4.2e-7 on gradient entries up to
# 0.59), so 1e-5 of the loss and 1e-5 on gradients. bfloat16: both sides hand
# a bf16 dlogits to the two gradient matmuls; the rule then rounds dw to bf16
# once a chunk where plain autodiff rounds it once in all (1.2e-3 to 3.3e-3
# on entries up to 0.59; the loss as in float32): the file's 3e-2 / 6e-2
# hold that and fail a dropped chunk, which moves entries by their own size.
@pytest.mark.parametrize("t,chunks", [(T, 4), (T - 2, 1)])
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 3e-2, 6e-2)])
def test_the_heads_rule_is_plain_autodiff(dtype, loss_tol, grad_tol, t, chunks):
    h, head, labels, valid = _head_inputs(t)
    assert lm_head._loss_chunks(h, labels, valid)[0].shape[0] == chunks

    def mean_loss(body):
        def f(h, head):
            loss, correct = body(h, head, labels, valid, dtype)
            return loss / valid.sum(), correct
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)

    (loss, correct), (dh, dw) = mean_loss(lm_head._head_loss)(h, head)
    (want, want_correct), (want_dh, want_dw) = mean_loss(_plain_head_loss)(h, head)
    assert abs(float(loss) - float(want)) <= loss_tol * max(1.0, float(want))
    assert float(correct) == float(want_correct)
    assert dh.dtype == dw.dtype == jnp.float32
    assert _gap((dh, dw), (want_dh, want_dw)) <= grad_tol
    assert float(jnp.max(jnp.abs(want_dw))) > 0.1
    # rows outside the loss move nothing
    assert bool(jnp.all(dh[valid == 0] == 0.0)) and float(valid.min()) == 0.0
    # the undifferentiated call is the same forward pass
    plain_call = lm_head._head_loss(h, head, labels, valid, dtype)
    assert float(plain_call[0]) == pytest.approx(float(loss * valid.sum()), rel=1e-6)
    assert float(plain_call[1]) == float(correct)


def test_the_tasks_gradients_with_the_rule_are_those_of_plain_autodiff(
        monkeypatch):
    """Through ``next_token_task(...).loss`` the head's cotangent is one over
    the count, not 1, and the rule sits under a ``lax.map`` over two rows:
    every parameter's gradient is what the plain head gives in its place."""
    from fedtpu.training.task import build_task
    _, stats_fn = build_model(TINY)
    task = build_task(TINY, stats_fn, TINY.vocab_size)
    p = _params()
    x = jnp.asarray(np.stack([_row(1), _row(2, docs=(20,))]))
    args = (p, x, None, jnp.ones((2,)))
    (loss, stats), g = jax.value_and_grad(task.loss, has_aux=True)(*args)
    assert float(stats["count"]) == (12 + 14 - 2) + (20 - 1)
    monkeypatch.setattr(olmoe, "_head_loss", _plain_head_loss)
    (want, _), want_g = jax.value_and_grad(task.loss, has_aux=True)(*args)
    assert abs(float(loss) - float(want)) <= 1e-5
    assert _gap(g, want_g) <= 1e-6
    assert float(jnp.max(jnp.abs(want_g["head"]))) > 1e-2


def _vocab_dots(jaxpr, vocab, scans=()):
    """For every ``dot_general`` with a vocabulary-sized dimension, here or
    in a nested jaxpr: the scans it sits in."""
    found = []
    for eqn in jaxpr.eqns:
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        if eqn.primitive.name == "dot_general" and any(vocab in s for s in shapes):
            found.append(scans)
        inner = scans + (id(eqn),) if eqn.primitive.name == "scan" else scans
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _vocab_dots(sub, vocab, inner)
    return found


def test_the_head_multiplies_over_the_vocabulary_three_times_a_chunk_not_four():
    """What says the rule engaged: the gradient's program holds the logits
    matmul and the two gradient matmuls in ONE scan (a checkpointed scan
    holds four in two), and the undifferentiated call holds the logits
    matmul alone."""
    h, head, labels, valid = _head_inputs(T)
    f = lambda h, head: lm_head._head_loss(h, head, labels, valid, jnp.bfloat16)
    grad = jax.make_jaxpr(jax.grad(lambda h, head: f(h, head)[0], argnums=(0, 1)))
    dots = _vocab_dots(grad(h, head).jaxpr, TINY.vocab_size)
    assert len(dots) == 3 and len(set(dots)) == 1 and len(dots[0]) == 1, dots
    forward = _vocab_dots(jax.make_jaxpr(f)(h, head).jaxpr, TINY.vocab_size)
    assert len(forward) == 1 and len(forward[0]) == 1, forward
    # the whole model's gradient holds no other: the embedding is a gather
    whole = jax.make_jaxpr(jax.grad(
        lambda p, row: _sys_loss(TINY, jnp.bfloat16)(p, row)[0]))
    assert len(_vocab_dots(whole(_params(), jnp.asarray(_row(3))).jaxpr,
                           TINY.vocab_size)) == 3


def test_forward_mode_through_the_head_is_refused():
    h, head, labels, valid = _head_inputs(T)
    f = lambda h: lm_head._head_loss(h, head, labels, valid, jnp.float32)[0]
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(f, (h,), (h,))


def test_masked_rows_count_for_nothing():
    _, stats_fn = build_model(TINY)
    p = _params()
    x = jnp.asarray(np.stack([_row(1), _row(2), _row(3)]))
    full = stats_fn(p, x[:2], jnp.ones((2,)))
    padded = stats_fn(p, x, jnp.asarray([1.0, 1.0, 0.0]))
    for k in full:
        np.testing.assert_allclose(padded[k], full[k], rtol=1e-6)


# The fused attention body (the library's tiled kernel, interpreted on the
# CPU) against the XLA body, which defines what is computed. Two blocks a
# side, so the online softmax over several key blocks and the causal skip
# are what runs, as at 4,096 on the chip; always under jit (the interpreter
# is not for eager use).
def _core_and_gradients(body, dtype, segs):
    def f(q, k, v, w):
        core = lambda q, k, v: body(q.astype(dtype), k.astype(dtype),
                                    v.astype(dtype), segs)
        grads = jax.grad(lambda q, k, v: (core(q, k, v) * w).sum(),
                         argnums=(0, 1, 2))(q, k, v)
        return core(q, k, v), grads
    return jax.jit(f)


# float32: the two bodies differ by rounding alone (over 20 seeds 4.8e-7 to
# 9.5e-7 on ctx, 1.5e-6 to 3.8e-6 on the gradients), so 1e-5. bfloat16: the
# kernel returns ctx in bf16 (half a unit in the last place of entries up to
# 4.3 is 7.8e-3) and rounds its probabilities before their sum is divided
# out: over 20 seeds 8.5e-3 to 1.36e-2 on ctx, 1.6e-2 to 3.1e-2 on gradient
# entries up to 6.4. 3e-2 / 6e-2 hold that and fail a dropped or doubled
# block, which moves entries by their own size.
@pytest.mark.parametrize("dtype,ctx_tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 3e-2, 6e-2)])
def test_the_fused_attention_body_is_the_xla_body(dtype, ctx_tol, grad_tol):
    docs = tuple(int(FUSED_T * share) for share in (0.27, 0.39, 0.2))
    segs = jnp.asarray(_row(0, docs, t=FUSED_T)[1])
    assert int(segs.max()) == 3 and int((segs == 0).sum()) > 0
    args = [jax.random.normal(k, (FUSED_T, FUSED_HEADS, FUSED_D))
            for k in jax.random.split(jax.random.key(4), 4)]
    with pltpu.force_tpu_interpret_mode():
        ctx, grads = _core_and_gradients(attn._fused_attention, dtype, segs)(*args)
    want, want_grads = _core_and_gradients(attn._xla_attention, dtype, segs)(*args)
    assert ctx.dtype == want.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(ctx - want))) <= ctx_tol
    assert _gap(grads, want_grads) <= grad_tol
    assert float(jnp.max(jnp.abs(want_grads[0]))) > 1.0


def test_a_row_of_padding_alone_is_finite_and_moves_nothing_when_fused(
        fused_on_the_cpu):
    p, row = _params(FUSED), jnp.zeros((2, FUSED_T), jnp.int32)
    f = jax.jit(_summed_loss_and_gradients(FUSED))
    (loss, s), g = f(p, [row])
    assert float(loss) == 0.0 and float(s[0]["count"]) == 0.0
    assert float(s[0]["padding"]) == FUSED_T
    assert all(bool(jnp.all(a == 0.0)) for a in jax.tree.leaves(g))
    args = [jax.random.normal(k, (FUSED_T, FUSED_HEADS, FUSED_D))
            for k in jax.random.split(jax.random.key(5), 4)]
    ctx, grads = _core_and_gradients(attn._fused_attention, jnp.bfloat16,
                                     row[1])(*args)
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in (ctx, *grads))


@pytest.mark.parametrize("backend,q,v,fused,core", [
    ("tpu", (4096, 16, 128), (4096, 16, 128), True, True),
    ("tpu", (32, 4, 8), (32, 4, 8), False, False),          # the tests' heads
    ("tpu", (4096 + 128, 16, 128), (4096 + 128, 16, 128), False, False),
    # q, k wider than v (latent attention): no kernel for the widths as they
    # are; the core pads them to one lane-wide width and runs it
    ("tpu", (4096, 16, 192), (4096, 16, 128), False, True),
    ("cpu", (4096, 16, 192), (4096, 16, 128), False, False),
    ("cpu", (4096, 16, 128), (4096, 16, 128), False, False)])
def test_the_rule_between_the_attention_bodies(monkeypatch, backend, q, v,
                                               fused, core):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    assert attn.fused_attention_applies(sds(q), sds(q), sds(v)) is fused
    ran = []
    for name in ("_fused_attention", "_xla_attention"):
        body = getattr(attn, name)
        monkeypatch.setattr(attn, name, lambda *a, _name=name, _body=body: (
            ran.append((_name, a[0].shape[-1])), _body(*a))[1])
    # either body traces at these shapes, and gives (T, heads, v's width)
    ctx = jax.eval_shape(
        lambda *a: attn.attention_core(*a, jnp.bfloat16), sds(q), sds(q),
        sds(v), jax.ShapeDtypeStruct(q[:1], jnp.int32))
    # the tiled body at one width for q, k and v, the XLA body as they are
    assert ran == [("_fused_attention", -(-q[2] // 128) * 128) if core
                   else ("_xla_attention", q[2])]
    assert ctx.shape == q[:2] + v[2:] and ctx.dtype == jnp.float32


# The Pallas body of the expert matmuls (the library's grouped kernels,
# interpreted on the CPU, always under jit) against ``lax.ragged_dot``, which
# defines what is computed: values and both gradients, over four row tiles
# and eight groups.
ROWS = 4 * grouped.GROUPED_ROW_TILE
GROUP_SIZES = {
    # an empty first, middle and last group; one under a row tile; one that
    # starts 40 rows into the first tile, spans three and straddles two of
    # their edges; rows past the last group, which are zero
    "uneven": lambda tile: [0, 40, 2 * tile + 88, 100, 0, 200, 50, 0],
    # the same kinds of group, and no row past the last
    "exact": lambda tile: [tile // 2, 0, tile, tile + tile // 2, 6, 100, 22,
                           tile - 128],
    # every row in one group
    "one_group": lambda tile: [0, 0, 0, 4 * tile, 0, 0, 0, 0],
    # a held block as a layer-step fills it: a third of the rows, groups of
    # under a row tile, three of them empty
    "third": lambda tile: [0, 90, tile // 2 + 2, 0, 41, 60, 0, 20],
}


def _matmul_and_gradients(body, sizes):
    def f(xs, w, c):
        out, back = jax.vjp(lambda xs, w: body(xs, w, sizes), xs, w)
        return (out, *back(c))
    return jax.jit(f)


# The hybrid stack's widths scaled down by seven lanes: 2,688 = 21 x 128 to
# 384, 1,856 = 14.5 x 128 to 192, and its measured tiles with them (half the
# row tile; the contracted width whole and the output width in tiles that do
# not divide it, 192 = 128 + 64 as 1,856 = 640 + 640 + 576; ``tgmm`` a third
# of the one width by the whole of the other).
CUT_TILES = {
    ("forward", 384, 192): (128, 384, 128),
    ("forward", 192, 384): (128, 192, 128),
    ("input_gradient", 384, 192): (128, 384, 128),
    ("input_gradient", 192, 384): (128, 192, 128),
    ("weight_gradient", 384, 192): (128, 128, 192),
    ("weight_gradient", 192, 384): (128, 192, 128),
}


# float32: the same sums in tiles (measured 5e-7 of the largest entry on the
# values, 6e-7 on either gradient): 1e-5 of the largest entry. bfloat16: the
# forward kernels give the XLA body's float32 sums (1e-6 of the largest
# entry); the gradients come back in bf16 from a cotangent rounded to bf16,
# where the XLA body on the CPU multiplies the float32 one: one unit in the
# last place of the largest entries (0.25 on 60, 0.5 on 79), so 2**-7 of the
# largest. A dropped tile or a row given to the wrong group moves entries by
# their own size. The last two cases run at widths that are no whole number
# of their tiles (``CUT_TILES``), with groups of no rows and rows past the
# last group: what the kernel cuts off a tile is in no sum, and no column
# past a width is written. The four after them run at the Xing4.0 and
# Kimi-Linear stacks' own widths and the table's own tiles (PR 45), both
# products of a held expert, on a block filled to a third.
@pytest.mark.parametrize("groups,k,n", [
    *((name, 256, 128) for name in sorted(GROUP_SIZES)),
    ("uneven", 384, 192), ("uneven", 192, 384),
    ("third", 3584, 1024), ("third", 1024, 3584),
    ("third", 2304, 1024), ("third", 1024, 2304)])
@pytest.mark.parametrize("dtype,out_tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 1e-5, 2.0 ** -7)])
def test_the_pallas_grouped_matmul_is_ragged_dot(monkeypatch, dtype, out_tol,
                                                 grad_tol, groups, k, n):
    sizes = np.asarray(GROUP_SIZES[groups](grouped.GROUPED_ROW_TILE), np.int32)
    total = int(sizes.sum())
    assert (total == ROWS) == (groups in ("exact", "one_group"))
    assert total <= ROWS and (groups != "third" or total == ROWS // 3)
    at_its_own_widths = ("forward", k, n) in grouped._MEASURED_TILES
    for key, tiles in CUT_TILES.items():
        monkeypatch.setitem(grouped._MEASURED_TILES, key, tiles)
    cut = ("forward", k, n) in CUT_TILES
    assert at_its_own_widths or cut == bool(
        n % grouped._grouped_tiles("forward", k, n)[2]
        or k % grouped._grouped_tiles("input_gradient", n, k)[2])
    keys = jax.random.split(jax.random.key(8), 3)
    xs = jax.random.normal(keys[0], (ROWS, k)).astype(dtype)
    w = jax.random.normal(keys[1], (len(sizes), k, n)).astype(dtype)
    c = jax.random.normal(keys[2], (ROWS, n))
    with pltpu.force_tpu_interpret_mode():
        got = _matmul_and_gradients(grouped._pallas_grouped_matmul,
                                    jnp.asarray(sizes))(xs, w, c)
    want = _matmul_and_gradients(grouped._xla_grouped_matmul,
                                 jnp.asarray(sizes))(xs, w, c)
    for a, b, tol in zip(got, want, (out_tol, grad_tol, grad_tol)):
        assert a.dtype == b.dtype and a.shape == b.shape
        b = b.astype(jnp.float32)
        largest = float(jnp.max(jnp.abs(b)))
        assert largest > 10.0
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) <= tol * largest
    out, dxs, dw = got
    assert out.dtype == jnp.float32 and dxs.dtype == dw.dtype == dtype
    # rows past the last group, and the weights of a group with no row
    assert bool(jnp.all(out[total:] == 0.0)) and bool(jnp.all(dxs[total:] == 0.0))
    assert bool(jnp.all(dw[sizes == 0] == 0.0)) and int((sizes == 0).sum()) >= 1


@pytest.mark.parametrize("backend,xs,w,dtype,pallas", [
    ("tpu", (32768, 2048), (64, 2048, 1024), jnp.bfloat16, True),
    ("tpu", (32768, 1024), (64, 1024, 2048), jnp.bfloat16, True),
    ("tpu", (64, 32), (8, 32, 16), jnp.bfloat16, False),     # the tests' widths
    ("tpu", (32768 + 128, 2048), (64, 2048, 1024), jnp.bfloat16, False),
    ("tpu", (32768, 2048), (64, 2048, 1408), jnp.bfloat16, False),
    ("tpu", (32768, 4096), (64, 4096, 1024), jnp.bfloat16, False),
    ("tpu", (32768, 2048), (64, 2048, 1024), jnp.float32, False),
    ("cpu", (32768, 2048), (64, 2048, 1024), jnp.bfloat16, False),
    # the hybrid stack's held experts: a block of the buffer, both products
    ("tpu", (8192, 2688), (8, 2688, 1856), jnp.bfloat16, True),
    ("tpu", (8192, 1856), (8, 1856, 2688), jnp.bfloat16, True),
    ("tpu", (8192, 2688), (8, 2688, 1856), jnp.float32, False),
    ("cpu", (8192, 1856), (8, 1856, 2688), jnp.bfloat16, False),
    # a measured width beside one it was not measured with
    ("tpu", (8192, 2688), (8, 2688, 1024), jnp.bfloat16, False),
    # the Xing4.0 and Kimi-Linear stacks' held experts (PR 45): a block of
    # each cell's own rows, the products into and out of the experts
    ("tpu", (5632, 3584), (8, 3584, 1024), jnp.bfloat16, True),
    ("tpu", (5632, 1024), (8, 1024, 3584), jnp.bfloat16, True),
    ("tpu", (2816, 2304), (8, 2304, 1024), jnp.bfloat16, True),
    ("tpu", (2816, 1024), (8, 1024, 2304), jnp.bfloat16, True),
    ("tpu", (5632, 3584), (8, 3584, 1024), jnp.float32, False),
    ("cpu", (2816, 1024), (8, 1024, 2304), jnp.bfloat16, False),
    ("tpu", (2816 + 128, 2304), (8, 2304, 1024), jnp.bfloat16, False),
    # each measured with 1,024, not with the other
    ("tpu", (5632, 3584), (8, 3584, 2304), jnp.bfloat16, False)])
def test_the_rule_between_the_grouped_matmul_bodies(monkeypatch, backend, xs,
                                                    w, dtype, pallas):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    sds = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt)
    assert grouped.grouped_matmul_applies(sds(xs), sds(w)) is pallas
    ran = []
    for name in ("_pallas_grouped_matmul", "_xla_grouped_matmul"):
        body = getattr(grouped, name)
        monkeypatch.setattr(grouped, name, lambda *a, _name=name, _body=body: (
            ran.append(_name), _body(*a))[1])
    # either body traces at these shapes, and gives (rows, N) float32
    out = jax.eval_shape(lambda *a: grouped.grouped_matmul(*a), sds(xs), sds(w),
                         sds(w[:1], jnp.int32))
    assert ran == ["_pallas_grouped_matmul" if pallas else "_xla_grouped_matmul"]
    assert out.shape == (xs[0], w[2]) and out.dtype == jnp.float32


# OLMoE's tiles are PR 29's to the number (its round program is compared
# with the parent's whenever this rule changes); the hybrid stack's are the
# table's, from the sweep of PR 33, the Xing4.0 and Kimi-Linear stacks' from
# the sweep of PR 45. Every tile is whole lanes or the whole width (what a
# block of a Mosaic kernel may be), and its rows divide the row tile the
# callers' buffers are whole numbers of.
@pytest.mark.parametrize("kernel,k,n,tiles", [
    ("forward", 2048, 1024, (256, 2048, 1024)),
    ("forward", 1024, 2048, (256, 1024, 2048)),
    ("input_gradient", 1024, 2048, (256, 1024, 2048)),
    ("input_gradient", 2048, 1024, (256, 2048, 1024)),
    ("weight_gradient", 2048, 1024, (256, 1024, 1024)),
    ("weight_gradient", 1024, 2048, (256, 1024, 1024)),
    *((*key, tiles) for key, tiles in sorted(grouped._MEASURED_TILES.items()))])
def test_the_tiles_of_the_grouped_kernels(kernel, k, n, tiles):
    assert grouped._grouped_tiles(kernel, k, n) == tiles
    tm, tk, tn = tiles
    assert grouped.GROUPED_ROW_TILE % tm == 0
    assert all(tile % 128 == 0 or tile == width
               for tile, width in ((tk, k), (tn, n)))
