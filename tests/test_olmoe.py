"""OLMoE (fedtpu.models.olmoe) against the plain reference
(perfbench/reference_lm.py: float32 at 'highest', dense experts, whole
logits), on seeded weights at a tiny size."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.config import ModelConfig
from fedtpu.models import olmoe
from fedtpu.models.registry import build_model
from perfbench import reference_lm

TINY = ModelConfig(kind="olmoe", hidden_size=32, num_attention_heads=4,
                   num_hidden_layers=1, num_experts=8, num_experts_per_tok=2,
                   intermediate_size=16, vocab_size=64)
T = 32
REF_CFG = {k: getattr(TINY, k) for k in
           ("num_attention_heads", "num_experts_per_tok", "rope_theta",
            "rms_norm_eps", "norm_topk_prob")}


@pytest.fixture(autouse=True)
def _small_loss_chunks(monkeypatch):
    # four chunks of the 32-token rows, so the chunked loss is what runs
    monkeypatch.setattr(olmoe, "LOSS_CHUNK", 8)


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
        s = olmoe.olmoe_sequence_stats(p, row, cfg, dtype)
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
@pytest.mark.parametrize("dtype,loss_tol,grad_tol", [
    (jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 3e-2, 6e-2)])
def test_loss_and_gradients_match_the_reference(dtype, loss_tol, grad_tol):
    p, row = _params(), jnp.asarray(_row(3))
    (loss, stats), g = jax.value_and_grad(_sys_loss(TINY, dtype), has_aux=True)(p, row)
    (ref, (ref_sum, ref_count)), rg = jax.value_and_grad(_ref_loss, has_aux=True)(p, row)
    assert float(stats["count"]) == float(ref_count) == 12 + 14 - 2
    assert float(stats["padding"]) == T - 26
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


def test_dropless_under_a_skew_over_four_times_the_mean():
    cfg = dataclasses.replace(TINY, num_experts=16)
    p = _params(cfg)
    # every token prefers expert 3: hidden states made positive (positive
    # embeddings, no attention output, unit gain) meet a positive column
    p["embed"] = jnp.abs(p["embed"])
    p["layers"]["o"] = jnp.zeros_like(p["layers"]["o"])
    p["layers"]["mlp_norm"] = jnp.ones_like(p["layers"]["mlp_norm"])
    p["layers"]["router"] = p["layers"]["router"].at[0, :, 3].set(0.5)
    row = jnp.asarray(_row(7, docs=(T,)))
    (loss, stats), g = jax.value_and_grad(_sys_loss(cfg, jnp.float32), has_aux=True)(p, row)
    load = np.asarray(stats["expert_load"])
    routed = int(load.sum())
    assert load[3] == T and load[3] > 4 * load.mean()   # 8x the mean here
    assert routed == cfg.num_experts_per_tok * T    # every assignment computed
    dropped = cfg.num_experts_per_tok * int(T - stats["padding"]) - routed
    assert dropped == 0
    (ref, _), rg = jax.value_and_grad(_ref_loss, has_aux=True)(p, row)
    assert abs(float(loss) - float(ref)) <= 1e-5 and _gap(g, rg) <= 1e-5


def test_two_packed_documents_give_what_the_two_alone_give():
    p = _params()
    both = _row(11, docs=(12, 14))
    alone = []
    for i, (a, b) in enumerate(((0, 12), (12, 26))):
        r = np.zeros_like(both)
        r[:, :b - a] = both[:, a:b]
        r[1, :b - a] = 1
        alone.append(jnp.asarray(r))

    def total(p, rows):
        s = [olmoe.olmoe_sequence_stats(p, r, TINY) for r in rows]
        return sum(x["loss_sum"] for x in s), s

    (packed, _), g = jax.value_and_grad(total, has_aux=True)(p, [jnp.asarray(both)])
    (apart, s), ga = jax.value_and_grad(total, has_aux=True)(p, alone)
    assert abs(float(packed) - float(apart)) <= 1e-4 * abs(float(apart))
    assert _gap(g, ga) <= 1e-5
    assert [float(x["count"]) for x in s] == [11.0, 13.0]


def test_depth_two_scanned_is_two_blocks_by_hand():
    cfg = dataclasses.replace(TINY, num_hidden_layers=2)
    p, row = _params(cfg), jnp.asarray(_row(13))
    got = olmoe.olmoe_sequence_stats(p, row, cfg)
    tokens, segs = row
    pos = olmoe.segment_positions(segs)
    h = p["embed"][tokens]
    for i in range(2):
        h, _ = olmoe._block(cfg, jnp.float32, h,
                            jax.tree.map(lambda a: a[i], p["layers"]), segs, pos)
    labels, valid = olmoe.next_token_targets(tokens, segs)
    want, _ = olmoe._head_loss(olmoe.rms_norm(h, p["final_norm"], cfg.rms_norm_eps),
                               p["head"], labels, valid, jnp.float32)
    assert abs(float(got["loss_sum"]) - float(want)) <= 1e-5
    with jax.default_matmul_precision("highest"):
        ref, _ = reference_lm.sequence_loss(p, row, REF_CFG)
    assert abs(float(got["loss_sum"]) - float(ref)) <= 1e-4


def test_masked_rows_count_for_nothing():
    _, stats_fn = build_model(TINY)
    p = _params()
    x = jnp.asarray(np.stack([_row(1), _row(2), _row(3)]))
    full = stats_fn(p, x[:2], jnp.ones((2,)))
    padded = stats_fn(p, x, jnp.asarray([1.0, 1.0, 0.0]))
    for k in full:
        np.testing.assert_allclose(padded[k], full[k], rtol=1e-6)
