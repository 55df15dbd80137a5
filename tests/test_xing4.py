"""The Xing4.0 stack, fedtpu.models.xing4, against its plain reference
(perfbench/reference_xing4.py), and both against the published code on this
machine where there is any (``transformers``' DeepseekV3Attention): two
federated rounds through ``run_experiment`` (the main and the module's loss
of every client, every global parameter); the loss and every gradient on
packed rows; the shares of a gated expert layer adding up to the uncut
layer; latent attention (RoPE restarting at a document's edge, the scale
with ``mscale^2``, the padded tiled core against the XLA body); the residual
path (doubly stochastic to the iteration's own residual, gradients through
the Sinkhorn loop, each part of the mix mattering, one stream with the mix
switched off being a pre-norm block); the prediction module (targets at
document edges, no module reproducing the main loss to the bit, where the
loss's weight enters the gradient); the parameter count of the published
configuration."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from fedtpu.config import ModelConfig, TelemetryConfig, get_preset
from fedtpu.models import layers, xing4
from fedtpu.models.registry import build_model
from fedtpu.ops import hyper_conn, lm_head
from fedtpu.ops import packed_attention as attn
from fedtpu.orchestration.loop import build_experiment, run_experiment
from fedtpu.training import task as task_mod
from fedtpu.training.task import build_task
from perfbench import flops_xing4, reference_xing4 as ref

T = 64
TINY = ModelConfig(
    kind="xing4", hidden_size=48, num_attention_heads=4, num_hidden_layers=3,
    first_k_dense_replace=1, num_nextn_predict_layers=1,
    intermediate_size=96, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, experts_held=4, first_expert=4,
    moe_intermediate_size=24, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=2.0, rms_norm_eps=1e-6, vocab_size=128)
ROPE_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")
REFERENCE_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                  "norm_topk_prob", "routed_scaling_factor", "hc_mult",
                  "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                  "mhc_h_res_clamp_max", "rms_norm_eps", "rope_theta",
                  "first_expert")


def ref_cfg(cfg):
    """The reference's dictionary of a ModelConfig: the published keys, the
    ``rope_scaling`` group nested as ``config.json`` has it, and the
    weight the task gives the module's loss."""
    return {**{k: getattr(cfg, k) for k in REFERENCE_KEYS},
            "mtp_loss_weight": task_mod.MTP_LOSS_WEIGHT,
            "rope_scaling": {k: getattr(cfg, f"rope_scaling_{k}")
                             for k in ROPE_KEYS}}


def packed_row(rng, lengths, vocab=128, t=T):
    row = np.zeros((2, t), np.int32)
    at = 0
    for seg, n in enumerate(lengths, start=1):
        row[0, at:at + n] = rng.integers(1, vocab, n)
        row[1, at:at + n] = seg
        at += n
    return row


def seeded(cfg, seed=0):
    """Seeded weights with every norm gain away from one, so that no
    gradient is checked at a special point."""
    params = build_model(cfg)[0](jax.random.key(seed))
    count = iter(range(10_000))

    def jitter(path, leaf):
        if "norm" not in jax.tree_util.keystr(path):
            return leaf
        return leaf + 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.key(seed + 1), next(count)),
            leaf.shape)

    return jax.tree_util.tree_map_with_path(jitter, params)


def _gap(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def tiny_xing4(rounds=2, modules=1, one_step_kind=True, **run):
    cfg = get_preset("xing4-29b-a4b-l5-mtp1")
    assert cfg.fed.one_step_kind        # the preset's: one trace of the model
    return cfg.replace(
        model=dataclasses.replace(
            TINY, num_nextn_predict_layers=modules, first_expert=8,
            compute_dtype="float32"),
        data=dataclasses.replace(cfg.data, synthetic_rows=10,
                                 synthetic_features=48),
        shard=dataclasses.replace(cfg.shard, num_clients=4),
        optim=dataclasses.replace(cfg.optim, learning_rate=0.1),
        fed=dataclasses.replace(cfg.fed, rounds=rounds, init_seed=3,
                                one_step_kind=one_step_kind),
        run=dataclasses.replace(cfg.run, mesh_devices=1, **run))


# --------------------------------------- (a) the normal path, two rounds
# float32 on both sides: the gaps are the order of the sums (2e-6 to 6e-6 on
# losses near 4.9 and on parameters that moved by 2e-2 over three seeds), so
# 2e-5, as the other two language models' rounds. With the preset's one kind
# of step, and with the engine's four (shards of 1, 2, 3 and 4 rows have all).
@pytest.mark.parametrize("modules,one_step_kind", [
    (1, True), (0, True), (1, False)],
    ids=["module", "no-module", "module-four-kinds-of-step"])
def test_two_rounds_through_run_experiment_match_the_references_fedavgm(
        tmp_path, modules, one_step_kind):
    sink = str(tmp_path / "ev.jsonl")
    cfg = tiny_xing4(modules=modules, one_step_kind=one_step_kind,
                     telemetry=TelemetryConfig(events_path=sink))
    result = run_experiment(cfg, verbose=False)
    ds = build_experiment(cfg).dataset
    rows = [ds.x_train[ds.client_of_row == c] for c in range(4)]
    assert sorted(len(r) for r in rows) == [1, 2, 3, 4]         # size skew
    init = jax.tree.map(np.asarray, build_model(cfg.model)[0](
        jax.random.key(cfg.fed.init_seed)))
    assert len(init["mtp"]) == modules
    want, ref_params = ref.fedavgm_rounds(
        init, rows, 2, ref_cfg(cfg.model),
        learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr)
    assert np.max(np.abs(np.stack(result.loss) - want["loss"])) <= 2e-5
    if modules:
        for ours, theirs in (("main_loss", "main"), ("mtp_loss", "mtp")):
            got = np.stack(result.per_client_metrics[ours])
            assert np.max(np.abs(got - want[theirs])) <= 2e-5, ours
        # the loss that is differentiated is the one, plus 0.3 of the other
        np.testing.assert_allclose(
            want["loss"][:, 0], want["main"][:, 0] + 0.3 * want["mtp"][:, 0],
            rtol=1e-6)           # client 0 has one step: means of one
    else:
        assert "mtp_loss" not in result.per_client_metrics
        np.testing.assert_allclose(want["loss"], want["main"], rtol=1e-6)
    assert _gap(result.final_params, ref_params) <= 2e-5
    assert _gap(result.final_params, init) > 1e-3               # it moved
    events = [json.loads(line) for line in open(sink)]
    snapshot = [e for e in events if e["kind"] == "counters"][-1]["payload"]
    counted, gauges = snapshot["counters"], snapshot["gauges"]
    tokens = int((ds.x_train[:, 1] > 0).sum())
    blocks = 2 + modules            # expert blocks; attention runs in 3 + modules
    assert counted["moe_assignments_total"] == 2 * blocks * 4 * tokens
    assert 0 < counted["moe_assignments_held"] < counted["moe_assignments_total"]
    assert counted["moe_tokens_dropped"] == 0
    assert counted["stateless_client_steps"] == 2 * 10
    assert counted["stateless_working_copy_writes"] == 2 * (
        10 if one_step_kind else 10 - 4)
    assert counted["hc_mix_positions"] == 2 * 10 * 48 * 2 * (3 + modules)
    assert counted["lm_fused_attention_positions"] == 0     # a CPU
    assert counted["hc_fused_positions"] == 0       # the definitions ran
    assert gauges["attention_padded_width"] == 0
    assert 0 < gauges["hc_sinkhorn_residual"] < 0.05
    if modules:
        segs = ds.x_train[:, 1]
        ahead = lambda by: np.pad(segs, ((0, 0), (0, by)))[:, by:]
        valid = (segs > 0) & (ahead(1) == segs) & (ahead(2) == segs)
        assert counted["mtp_positions"] == 2 * int(valid.sum())
        # the last round's two parts, each near ln(vocabulary) still
        assert all(abs(gauges[name] - np.log(128)) < 0.5
                   for name in ("main_loss", "mtp_loss"))
    else:
        assert "mtp_positions" not in counted and "mtp_loss" not in gauges


# ----------------------------------- the loss and every gradient, one step
def test_the_loss_and_every_gradient_are_the_references():
    """Rows of two and three packed documents and padding, jittered gains,
    float32: each part of the loss to 1e-5 and every leaf's gradient to 5e-5
    of the leaf's largest entry (the order of the sums; the largest seen is
    1.2e-5, on a residual module's ``alpha``, a sum over every position and
    column of terms of both signs)."""
    params = seeded(TINY)
    rng = np.random.default_rng(0)
    task = build_task(TINY, build_model(TINY)[1], 128)
    grad = jax.jit(jax.value_and_grad(task.loss, has_aux=True))
    for lengths in ((20, 30), (17, 23, 19)):
        row = jnp.asarray(packed_row(rng, lengths))
        (loss, stats), g = grad(params, row[None], None, jnp.ones((1,)))
        with jax.default_matmul_precision("highest"):
            (want, sums), rg = jax.value_and_grad(
                lambda q: ref.mean_loss(q, row, ref_cfg(TINY)),
                has_aux=True)(params)
        assert abs(float(loss) - float(want)) <= 1e-5
        for ours, theirs in zip(("loss_sum", "count", "mtp_loss_sum",
                                 "mtp_count"), sums):
            np.testing.assert_allclose(float(stats[ours]), float(theirs),
                                       rtol=2e-6)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                                jax.tree.leaves(rg)):
            scale = max(float(jnp.abs(b).max()), 1e-6)
            assert float(jnp.abs(a - b).max()) <= 5e-5 * scale + 1e-9, (
                jax.tree_util.keystr(path))
        # no gradient reaches a selection bias
        assert all(float(jnp.abs(layer["ffn"]["router_bias"]).max()) == 0.0
                   for layer in g["experts"])


# ------------------------------------------------ (b) the shares add up
def test_the_shares_of_a_gated_expert_layer_add_up_to_the_uncut_layer():
    """16 routed gated experts in 4 shares of 4: the four partial results,
    with the shared expert (which every chip computes alike) counted once,
    are the uncut reference layer's."""
    whole = dataclasses.replace(TINY, experts_held=0, first_expert=0)
    key = jax.random.key(7)
    count = iter(range(100))
    layer = layers._ffn_init(
        "experts", whole, lambda *s: 0.3 * jax.random.normal(
            jax.random.fold_in(key, next(count)), s),
        lambda *s: jnp.ones(s))
    layer["norm"] = layer["norm"] + 0.1 * jax.random.normal(key, (48,))
    h = jax.random.normal(jax.random.key(8), (T, 48))
    segs = jnp.asarray([1] * 30 + [2] * 34, jnp.int32)
    x = ref._rms(h, layer["norm"], 1e-6)
    with jax.default_matmul_precision("highest"):
        uncut = ref.experts(layer, x, ref_cfg(whole))
        shared = ref.gated(x, layer["shared_gate"], layer["shared_up"],
                           layer["shared_down"])
    total, held_sum = 0.0, 0.0
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(TINY, experts_held=4, first_expert=first)
        part = {**layer, **{name: layer[name][first:first + 4]
                            for name in ("gate", "up", "down")}}
        out, stats = layers.experts_mixer(share, jnp.float32, h, part, segs,
                                      eps=1e-6)
        total, held_sum = total + out, held_sum + stats["assignments_held"]
        with jax.default_matmul_precision("highest"):
            want = ref.experts(part, x, ref_cfg(share))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=0, atol=5e-5)
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(uncut), rtol=0, atol=1e-4)
    assert float(held_sum) == 4 * T          # every assignment, exactly once
    assert float(jnp.abs(uncut - shared).max()) > 0.1    # the routed part is there


# -------------------------------------------------- (c) latent attention
def _attention_layer(cfg, seed=3):
    key = jax.random.key(seed)
    count = iter(range(100))
    layer = xing4._attention_init(
        cfg, lambda *s: 0.2 * jax.random.normal(
            jax.random.fold_in(key, next(count)), s),
        lambda *s: 1.0 + 0.1 * jax.random.normal(
            jax.random.fold_in(key, next(count)), s))
    return layer


def _program_attention(cfg, layer, u, segs):
    pos = layers.segment_positions(segs)
    return layers.latent_attention(cfg, jnp.float32, u, layer, segs, pos)


def test_latent_attention_is_transformers_deepseek_v3_attention():
    """The same weights through ``DeepseekV3Attention`` (eager, float32, a
    causal mask, positions from 0) and through the reference and the
    program, one document: 1e-5 on outputs up to 1. Then two documents
    packed into one row give what each gives alone through ``transformers``:
    positions restart and no score crosses the edge."""
    torch = pytest.importorskip("torch")
    try:
        from transformers.models.deepseek_v3.configuration_deepseek_v3 import \
            DeepseekV3Config
        from transformers.models.deepseek_v3.modeling_deepseek_v3 import (
            DeepseekV3Attention, DeepseekV3RotaryEmbedding)
    except Exception as exc:
        pytest.skip(f"transformers' DeepseekV3Attention cannot be imported: {exc!r}")
    scaling = {"type": "yarn", **ref_cfg(TINY)["rope_scaling"]}
    conf = DeepseekV3Config(
        hidden_size=48, num_attention_heads=4, num_key_value_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rms_norm_eps=1e-6,
        rope_theta=10000.0, rope_scaling=scaling, attention_bias=False,
        max_position_embeddings=262144)
    conf._attn_implementation = "eager"
    assert conf.rope_interleave
    layer = _attention_layer(TINY)
    module = DeepseekV3Attention(conf, layer_idx=0).eval()
    rotary = DeepseekV3RotaryEmbedding(conf)
    to_torch = lambda a: torch.tensor(np.asarray(a))
    with torch.no_grad():
        for ours, theirs in (("q_a", "q_a_proj"), ("q_b", "q_b_proj"),
                             ("kv_a", "kv_a_proj_with_mqa"),
                             ("kv_b", "kv_b_proj"), ("o", "o_proj")):
            getattr(module, theirs).weight.copy_(to_torch(layer[ours]).T)
        module.q_a_layernorm.weight.copy_(to_torch(layer["q_a_norm"]))
        module.kv_a_layernorm.weight.copy_(to_torch(layer["kv_a_norm"]))
    assert abs(module.scaling - layers.attention_scale(TINY)) < 1e-9
    assert abs(module.scaling - 24 ** -0.5 * (0.1 * np.log(64) + 1) ** 2) < 1e-9
    np.testing.assert_allclose(rotary.inv_freq.numpy(),
                               layers.yarn_inv_freq(TINY), rtol=1e-6)

    def published(x):
        """``transformers`` on one document ``x (n, 48)`` alone."""
        n = x.shape[0]
        xt = to_torch(x)[None]
        cos, sin = rotary(xt, torch.arange(n)[None])
        mask = torch.full((n, n), -1e30).triu(1)[None, None]
        with torch.no_grad():
            return module(xt, (cos, sin), mask)[0][0].numpy()

    u = jax.random.normal(jax.random.key(5), (T, 48))
    x = ref._rms(u, layer["norm"], 1e-6)
    one = jnp.ones((T,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        theirs = ref.attention(layer, x, one, ref_cfg(TINY))
    want = published(np.asarray(x))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(np.asarray(theirs), want, rtol=0, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(_program_attention(TINY, layer, u, one)), want, rtol=0,
        atol=1e-5)
    # two documents and padding in one row: each as transformers gives it alone
    segs = jnp.asarray([1] * 25 + [2] * 30 + [0] * 9, jnp.int32)
    packed = np.asarray(_program_attention(TINY, layer, u, segs))
    with jax.default_matmul_precision("highest"):
        packed_ref = np.asarray(ref.attention(layer, x, segs, ref_cfg(TINY)))
    for lo, hi in ((0, 25), (25, 55)):
        alone = published(np.asarray(x[lo:hi]))
        np.testing.assert_allclose(packed[lo:hi], alone, rtol=0, atol=1e-5)
        np.testing.assert_allclose(packed_ref[lo:hi], alone, rtol=0, atol=1e-5)
    # a token of the second document moved by its position in the ROW would
    # read otherwise: positions matter at this size
    shifted = published(np.asarray(x[:55]))[25:]
    assert np.abs(shifted - packed[25:55]).max() > 1e-3


def test_rope_positions_restart_and_the_scale_carries_mscale_squared():
    """Without ``transformers``: a document's attention does not change with
    what is packed before it, and the program's scale and frequencies are the
    reference's."""
    layer = _attention_layer(TINY)
    u = jax.random.normal(jax.random.key(6), (T, 48))
    segs = jnp.asarray([1] * 20 + [2] * 40 + [0] * 4, jnp.int32)
    packed = _program_attention(TINY, layer, u, segs)
    alone = _program_attention(TINY, layer, u[20:60],
                               jnp.ones((40,), jnp.int32))
    np.testing.assert_allclose(np.asarray(packed[20:60]), np.asarray(alone),
                               rtol=0, atol=1e-5)
    rc = ref_cfg(TINY)
    assert abs(layers.attention_scale(TINY) - ref.softmax_scale(rc)) < 1e-12
    assert abs(ref.softmax_scale(rc)
               - 24 ** -0.5 * (0.1 * np.log(64) + 1) ** 2) < 1e-9
    np.testing.assert_array_equal(layers.yarn_inv_freq(TINY),
                                  ref.yarn_inv_freq(rc))
    # YaRN at the published head: the fast columns keep their frequency,
    # the slow ones are divided by the factor
    inv = layers.yarn_inv_freq(get_preset("xing4-29b-a4b-l5-mtp1").model)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(inv[-4:], plain[-4:] / 64, rtol=1e-6)


# float32: the padded kernel against the XLA body at the head's own widths
# differ by rounding alone (4e-7 on ctx, 3e-6 on the gradients here), so
# 1e-5; the zero columns add nothing to a score and the cut columns of the
# context carry no cotangent back.
def test_the_padded_tiled_core_is_the_unpadded_xla_body(monkeypatch):
    t, heads, dq, dv = attn.ATTENTION_BLOCK, 2, 192, 128
    keys = jax.random.split(jax.random.key(4), 4)
    q, k = (jax.random.normal(key, (t, heads, dq)) for key in keys[:2])
    v, w = (jax.random.normal(key, (t, heads, dv)) for key in keys[2:])
    docs = (int(t * 0.3), int(t * 0.45), int(t * 0.2))
    segs = jnp.asarray(packed_row(np.random.default_rng(0), docs, t=t)[1])
    scale = 0.11

    def core_and_gradients(q, k, v):
        core = lambda q, k, v: attn.attention_core(q, k, v, segs,
                                                    jnp.float32, scale=scale)
        grads = jax.grad(lambda *a: (core(*a) * w).sum(), argnums=(0, 1, 2))(
            q, k, v)
        return core(q, k, v), grads

    jitted = jax.jit(core_and_gradients)
    want, want_grads = jitted(q, k, v)
    ran = []
    monkeypatch.setattr(attn, "fused_attention_applies", lambda *a: (
        ran.append(tuple(x.shape for x in a)), True)[1])
    steered = jax.jit(core_and_gradients)    # traced anew: the rule is read again
    with pltpu.force_tpu_interpret_mode():
        ctx, grads = steered(q, k, v)
    assert set(ran) == {((t, heads, 256),) * 3}         # one width, padded
    assert ctx.shape == (t, heads, dv) and ctx.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(ctx - want))) <= 1e-5
    assert _gap(grads, want_grads) <= 1e-5
    assert all(g.shape == a.shape for g, a in zip(grads, (q, k, v)))
    # and the scale is the caller's: the default would read otherwise
    plain = attn._xla_attention(q, k, v, segs)
    assert float(jnp.max(jnp.abs(plain - want))) > 1e-2


# ----------------------------------------------------- (d) the residual path
def _module_and_streams(cfg, seed=0, t=T):
    module = xing4._hyper_init(cfg, jax.random.key(seed), jnp.float32)
    n = cfg.hc_mult
    x = jax.random.normal(jax.random.key(seed + 1), (n, t, cfg.hidden_size))
    return module, x * jnp.arange(1, n + 1)[:, None, None]     # streams differ


def _reference_maps(cfg, module, x):
    with jax.default_matmul_precision("highest"):
        return ref.hyper_maps(x.transpose(1, 0, 2), module, ref_cfg(cfg))


def test_h_res_is_doubly_stochastic_to_the_iterations_own_residual():
    """After the last row normalisation the rows sum to one to ``hc_eps``;
    the columns to what 20 iterations leave (under 1e-2 from this start),
    falling with the iterations; and the maps are the reference's."""
    module, x = _module_and_streams(TINY)
    pre, post, res = hyper_conn.hyper_mix(x, module, TINY)
    assert pre.shape == (4, T) and post.shape == (4, T) and res.shape == (4, 4, T)
    assert float(jnp.abs(res.sum(axis=1) - 1.0).max()) <= 1e-5
    off = float(xing4.sinkhorn_residual(res))
    assert off <= 1e-2
    fewer = dataclasses.replace(TINY, hc_sinkhorn_iters=3)
    assert float(xing4.sinkhorn_residual(
        hyper_conn.hyper_mix(x, module, fewer)[2])) > 2 * off
    assert bool(jnp.all(res > 0)) and bool(jnp.all((pre > 0) & (pre < 1)))
    assert bool(jnp.all((post > 0) & (post < 2)))
    want = _reference_maps(TINY, module, x)
    for ours, theirs in zip((pre.T, post.T, res.transpose(2, 0, 1)), want):
        np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                                   rtol=0, atol=2e-6)


def test_gradients_through_the_sinkhorn_loop_are_the_references():
    """A sublayer around a fixed nonlinear map: the cotangents of the
    streams and of every leaf of the module, through the read, the write and
    the 20 iterations, against the reference's autodiff through its Python
    loop: 1e-5 of each gradient's largest entry."""
    module, x = _module_and_streams(TINY, seed=2)
    w = jax.random.normal(jax.random.key(9), (4, T, 48))
    fn = lambda u: jnp.tanh(u) * 2.0

    def ours(module, x):
        out, _, _ = xing4.sublayer(TINY, x, module, lambda u: (fn(u), {}))
        return (out * w).sum()

    def theirs(module, x):
        out = ref.sublayer(x.transpose(1, 0, 2), module, fn, ref_cfg(TINY))
        return (out.transpose(1, 0, 2) * w).sum()

    program = jax.jit(jax.grad(ours, argnums=(0, 1)))
    got = program(module, x)
    reference = jax.jit(jax.grad(theirs, argnums=(0, 1)))
    with jax.default_matmul_precision("highest"):
        want = reference(module, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        scale = float(jnp.abs(b).max())
        assert scale > 1e-4, jax.tree_util.keystr(path)  # every leaf is reached
        assert float(jnp.abs(a - b).max()) <= 1e-5 * scale, (
            jax.tree_util.keystr(path))


@pytest.mark.parametrize("zeroed", ["alpha", "bias", "phi"])
def test_the_static_and_the_dynamic_part_of_the_mix_both_matter(zeroed):
    """At the rehearsal's size and the start the program draws: without the
    dynamic part (``alpha`` or ``phi`` zero) or without the static one
    (``bias`` zero) a sublayer's output moves by more than a hundred times
    the comparison's tolerance (1e-5), and the four streams differ."""
    module, x = _module_and_streams(TINY, seed=4)
    run = lambda m: xing4.sublayer(TINY, x, m, lambda u: (jnp.tanh(u), {}))[0]
    full = run(module)
    without = run({**module, zeroed: jnp.zeros_like(module[zeroed])})
    assert float(jnp.abs(full - without).max()) > 1e-3
    e = jax.random.normal(jax.random.key(1), (T, 48))
    same = jnp.broadcast_to(e, (4, T, 48))          # as the embedding enters
    out = xing4.sublayer(TINY, same, module, lambda u: (jnp.tanh(u), {}))[0]
    assert float(jnp.abs(out[0] - out[1]).max()) > 1e-2


def test_one_stream_with_the_mix_switched_off_is_a_pre_norm_block():
    """``hc_mult = 1``, the dynamic part zero, ``H_pre = sigmoid(30) = 1``,
    ``H_post = 2 sigmoid(0) = 1``; Sinkhorn leaves a 1 x 1 matrix at ``1 -
    hc_eps``: the block is ``h + attention(norm(h))``, then ``+ mlp(norm(.))``,
    to 1e-5."""
    cfg = dataclasses.replace(TINY, hc_mult=1)
    layer = build_model(cfg)[0](jax.random.key(2))["dense"][0]
    off = {"phi": jnp.zeros((3, 48)), "alpha": jnp.zeros((3,)),
           "bias": jnp.asarray([30.0, 0.0, 0.0])}
    layer = {**layer, "attn_hc": off, "ffn_hc": off}
    h = jax.random.normal(jax.random.key(3), (T, 48))
    segs = jnp.asarray([1] * 40 + [2] * 24, jnp.int32)
    pos = layers.segment_positions(segs)
    out, _ = xing4.block("dense", cfg, jnp.float32, h[None], layer, segs, pos)
    want = h + layers.latent_attention(cfg, jnp.float32, h, layer["attn"], segs,
                                      pos)
    want = want + layers.dense_mlp(cfg, jnp.float32, want, layer["ffn"])
    assert out.shape == (1, T, 48)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), rtol=0,
                               atol=1e-5)


# -------------------------------------------------- (e) the prediction module
def test_the_modules_targets_and_validity_at_document_edges():
    tokens = jnp.arange(1, 13, dtype=jnp.int32)
    segs = jnp.asarray([1, 1, 1, 1, 2, 2, 3, 3, 3, 0, 0, 0], jnp.int32)
    labels, valid = xing4.mtp_targets(tokens * (segs > 0), segs)
    np.testing.assert_array_equal(labels[:7], np.arange(3, 10))
    # a document of four has two targets, of two none, of three one
    np.testing.assert_array_equal(
        valid, [1, 1, 0, 0, 0, 0, 1, 0, 0, 0, 0, 0])
    _, main = lm_head.next_token_targets(tokens, segs)
    docs = 3
    assert float(main.sum()) - float(valid.sum()) == docs   # one more a document


def test_no_module_reproduces_the_main_loss_and_its_weight_scales_the_gradient(
        monkeypatch):
    """The main stack's loss does not know the module is there (to the bit);
    a weight of zero leaves the main stack's gradients the no-module model's
    (to 1e-7 on entries up to 0.1) and the module's own zero; the module's
    part of a shared leaf's gradient is linear in the weight."""
    with_module = TINY
    none = dataclasses.replace(TINY, num_nextn_predict_layers=0)
    params = seeded(with_module)
    main_only = {**params, "mtp": ()}
    row = jnp.asarray(packed_row(np.random.default_rng(3), (20, 30)))[None]
    ones = jnp.ones((1,))

    def grads(cfg, p, weight=0.3):
        monkeypatch.setattr(task_mod, "MTP_LOSS_WEIGHT", weight)
        task = build_task(cfg, build_model(cfg)[1], 128)
        step = jax.jit(jax.value_and_grad(task.loss, has_aux=True))
        return step(p, row, None, ones)

    (loss0, stats0), g0 = grads(none, main_only)
    (loss1, stats1), g1 = grads(with_module, params)
    assert float(stats0["loss_sum"]) == float(stats1["loss_sum"])      # bitwise
    assert float(stats0["count"]) == float(stats1["count"])
    assert "mtp_loss_sum" not in stats0
    mtp = float(stats1["mtp_loss_sum"] / stats1["mtp_count"])
    assert abs(float(loss1) - float(loss0) - 0.3 * mtp) <= 1e-6
    (loss_off, _), g_off = grads(with_module, params, weight=0.0)
    assert float(loss_off) == float(loss0)
    # another program, the same sums in another order: last bits
    for name in ("embed", "dense", "experts", "final_norm", "head"):
        assert _gap(g_off[name], g0[name]) <= 1e-7, name
    assert all(float(jnp.abs(leaf).max()) == 0.0
               for leaf in jax.tree.leaves(g_off["mtp"]))
    # d loss / d head = main's + weight * module's
    (_, _), g2 = grads(with_module, params, weight=0.6)
    part = g1["head"] - g0["head"]
    assert float(jnp.abs(part).max()) > 1e-4
    np.testing.assert_allclose(np.asarray(g2["head"] - g0["head"]),
                               np.asarray(2 * part), rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g2["mtp"][0]["proj"]), np.asarray(2 * g1["mtp"][0]["proj"]),
        rtol=1e-4, atol=1e-8)


# ------------------------------------------------ (f) the published widths
def test_the_parameter_count_of_the_published_configuration():
    """The program's count, the configuration file's and
    ``flops_xing4.params`` agree, part by part (ISSUE 37's arithmetic)."""
    from perfbench.drivers import train_xing4

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs",
                           "xing4-29b-a4b-l5-mtp1-fed8.json")) as fh:
        conf = json.load(fh)
    preset = get_preset("xing4-29b-a4b-l5-mtp1").model
    fields = train_xing4.model_fields(conf)
    assert {k: getattr(preset, k) for k in fields} == fields
    shapes = jax.eval_shape(build_model(preset)[0], jax.random.key(0))
    size = lambda tree: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
    counted = flops_xing4.params(fields)
    assert size(shapes["dense"][0]["attn"]) - 3584 == counted["attention"] == 28_411_136
    assert size(shapes["dense"][0]["attn_hc"]) == counted["hyper_module"] == 344_091
    assert size(shapes["dense"][0]) == counted["dense_layer"] == 128_196_918
    assert size(shapes["experts"][0]) == counted["experts_layer"] == 128_426_358
    assert size(shapes["mtp"][0]) == counted["module"] == 154_127_222
    assert size({**shapes, "mtp": ()}) == counted["main"] == 759_346_446
    assert size(shapes) == counted["total"] == conf["parameters"] == 913_473_668
    assert conf["memory"]["engine_bytes"] == 12 * 913_473_668
    assert len(shapes["dense"]) == 1 and len(shapes["experts"]) == 4


def test_the_scopes_of_a_tiny_round_name_this_stacks_layers_and_its_module():
    """One walk of the compiled round's text: the three layers and the two
    pieces this stack brings are there, every operation under ``mtp`` keeps
    its own layer's name, and the module holds a block's layers and a head."""
    from fedtpu.analysis.program import program_scopes
    from fedtpu.orchestration import loop
    from fedtpu.parallel.round import (LAYERS, MODULES, PIECES, RECOMPUTE,
                                       SERVER_UPDATE, SGD_PASS, STAGES)
    exp = build_experiment(tiny_xing4())
    text = exp.make_step(1).lower(exp.state, exp.batch).compile().as_text()
    walk = program_scopes(
        text, STAGES + (loop.STATE_CHECK,), layers=LAYERS, pieces=PIECES,
        modules=MODULES, update=(SGD_PASS, SERVER_UPDATE),
        recompute=(RECOMPUTE,))
    layers, pieces, modules = walk["layers"], walk["pieces"], walk["modules"]
    assert {"attention", "hyper_conn", "dense_mlp", "shared_expert", "router",
            "expert_dispatch", "experts", "mtp_proj", "lm_head_loss",
            "server_update", "embed"} <= set(layers.values())
    assert {"attn_core", "attn_latent", "hc_sinkhorn", "sgd_pass"} <= set(
        pieces.values())
    # a piece lies inside its layer (a key is an instruction's name and
    # shape: two loop bodies' instructions of one name collide now and then)
    for names, layer in ((("hc_sinkhorn",), "hyper_conn"),
                         (("attn_core", "attn_latent"), "attention")):
        found = [layers[k] for k, piece in pieces.items()
                 if piece in names and k in layers]
        assert found and found.count(layer) >= 0.95 * len(found), (names, layer)
    inside = {layers[k] for k in modules if k in layers}
    assert set(modules.values()) == {"mtp"}
    assert {"attention", "hyper_conn", "experts", "mtp_proj",
            "lm_head_loss"} <= inside and "dense_mlp" not in inside
    assert 0 < len(modules) < len(layers)
    # a program that is asked for no module gives no such map
    assert "modules" not in program_scopes(text, STAGES, layers=LAYERS)


def test_the_residual_kernels_keep_their_scopes_in_a_tiny_round(
        tmp_path, hyper_passes_on_the_cpu):
    """The rule between the bodies told yes and the kernels interpreted
    (``jax.checkpoint`` a pass-through), a tiny round LOWERED names the four
    kernels on its operations' name stacks, each under ``hyper_conn`` (what
    ``x4_hyper_conn_ms`` reads) and in no piece, the two transposes in the
    backward pass, with the Sinkhorn turns still under ``hc_sinkhorn``; and
    the round RUN counts every position as fused (a block on the kernels
    against the plain one: ``tests/test_hyper_conn_kernels.py``). (The compiled round at published widths holds the same
    of the Mosaic calls themselves: ``tests/test_aot_tpu_compile.py``.)"""
    import re

    from fedtpu.analysis.program import BACKWARD, _pass_of, _stage_of
    from fedtpu.parallel.round import LAYERS, PIECES

    exp = build_experiment(tiny_xing4(rounds=1))
    text = exp.make_step(1).lower(exp.state, exp.batch).as_text(
        debug_info=True)
    names = set(re.findall(r'"([^"]*/hyper_conn_\w+/[^"]*)"', text))
    kernels = {re.search(r"hyper_conn_(\w+)", n).group(1) for n in names}
    assert kernels == {"mix_read_forward", "mix_read_backward",
                       "write_forward", "write_backward"}
    for name in names:
        assert _stage_of(name, LAYERS) == "hyper_conn", name
        assert _stage_of(name, PIECES) is None, name
        assert (_pass_of(name, (), ()) == BACKWARD) == (
            "_backward/" in name), name
    assert re.search(r'hyper_conn/hc_sinkhorn/', text)
    sink = str(tmp_path / "ev.jsonl")
    cfg = tiny_xing4(rounds=1, modules=0,
                     telemetry=TelemetryConfig(events_path=sink))
    cfg = cfg.replace(      # two blocks, a row a client: four modules a step
        model=dataclasses.replace(cfg.model, num_hidden_layers=2),
        data=dataclasses.replace(cfg.data, synthetic_rows=4))
    result = run_experiment(cfg, verbose=False)
    events = [json.loads(line) for line in open(sink)]
    counted = [e for e in events
               if e["kind"] == "counters"][-1]["payload"]["counters"]
    assert counted["hc_fused_positions"] == 4 * 48
    assert counted["hc_mix_positions"] == 4 * 48 * 2 * 2
    assert np.all(np.isfinite(np.stack(result.loss)))


def test_what_the_registry_refuses():
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        build_model(dataclasses.replace(TINY, first_k_dense_replace=4))
    with pytest.raises(ValueError, match="no or one"):
        build_model(dataclasses.replace(TINY, num_nextn_predict_layers=2))
    with pytest.raises(ValueError, match="not among the 16"):
        build_model(dataclasses.replace(TINY, first_expert=14))
