"""The Solar-Open2 stack (``kind="solar_open2"``: fedtpu.models.kimi_linear
with the gated grouped-query layer of fedtpu.models.layers leading a period
and ``beta = 2 sigmoid`` in its KDA mixers) against its plain reference
(perfbench/reference_solar_open2.py): one federated round through
``run_experiment`` (every client's loss, every global parameter, the new
counters); the loss and every gradient on packed rows; the gate (driven shut,
left out, a half); the mixer the hybrid stack and this one share, to the bit;
the step's factor; ``layer_kinds`` from the 0-based ``gqa_layers`` and its
errors; the head-shares and the expert-shares of one layer adding up to the
uncut reference's; the parameter count of the published configuration. (The
chunked recurrence against the token-by-token one with ``beta`` over (0, 2)
is three cases of tests/test_kimi_linear.py's parametrised test.)"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.config import ModelConfig, TelemetryConfig, get_preset
from fedtpu.models import kimi_linear as kl
from fedtpu.models import layers, nemotron_h
from fedtpu.models.registry import LANGUAGE_MODELS, build_model
from fedtpu.ops.packed_attention import attention_core
from fedtpu.orchestration.loop import build_experiment, run_experiment
from fedtpu.training.task import build_task
from perfbench import flops_solar_open2, reference_solar_open2 as ref

T = 128
TINY = ModelConfig(
    kind="solar_open2", hidden_size=48, num_attention_heads=8,
    num_key_value_heads=4, head_dim=16, num_hidden_layers=4, gqa_layers=(0,),
    use_gqa_gate=True, kda_allow_neg_eigval=True, kda_num_heads=8,
    kda_head_dim=16, short_conv_kernel_size=4, first_k_dense_replace=0,
    n_routed_experts=16, experts_held=4, first_expert=4,
    moe_intermediate_size=24, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=1.0, rms_norm_eps=1e-5, vocab_size=128)


def ref_cfg(cfg):
    """The reference's dictionary of a ModelConfig, under the published
    config's own key names."""
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "use_gqa_gate": cfg.use_gqa_gate,
            "kda_allow_neg_eigval": cfg.kda_allow_neg_eigval,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "first_expert": cfg.first_expert,
            "linear_attn_config": {
                "num_heads": cfg.kda_num_heads, "head_dim": cfg.kda_head_dim,
                "short_conv_kernel_size": cfg.short_conv_kernel_size}}


def packed_row(rng, lengths, vocab=128, t=T):
    row = np.zeros((2, t), np.int32)
    at = 0
    for seg, n in enumerate(lengths, start=1):
        row[0, at:at + n] = rng.integers(1, vocab, n)
        row[1, at:at + n] = seg
        at += n
    return row


def seeded(cfg, seed=0):
    """Seeded weights with every norm gain and the output gate's bias away
    from their start, so that no gradient is checked at a special point."""
    params = build_model(cfg)[0](jax.random.key(seed))
    count = iter(range(10_000))

    def jitter(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" not in name and "g_bias" not in name:
            return leaf
        return leaf + 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.key(seed + 1), next(count)),
            leaf.shape)

    return jax.tree_util.tree_map_with_path(jitter, params)


def _gap(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def tiny_solar_open2(rounds=1, **run):
    cfg = get_preset("solar-open2-250b-l4")
    assert cfg.fed.one_step_kind        # the preset's: one trace of the model
    return cfg.replace(
        model=dataclasses.replace(TINY, first_expert=8,
                                  compute_dtype="float32"),
        data=dataclasses.replace(cfg.data, synthetic_rows=10,
                                 synthetic_features=T),
        shard=dataclasses.replace(cfg.shard, num_clients=4),
        optim=dataclasses.replace(cfg.optim, learning_rate=0.1),
        fed=dataclasses.replace(cfg.fed, rounds=rounds, init_seed=3),
        run=dataclasses.replace(cfg.run, mesh_devices=1, **run))


# ----------------------------------------- the normal path, one round
def test_a_round_through_run_experiment_matches_the_references_fedavgm(
        tmp_path, monkeypatch):
    """float32 on both sides: the gaps are the order of the sums, so 2e-5 on
    losses near 4.9 and on parameters that moved by 1e-2, as the other
    language models' rounds. The counters this stack brings: the delta
    rule's steps (real positions x heads x KDA layers), those of them over
    1, about half at the start, and the gauge of the largest."""
    monkeypatch.setattr("fedtpu.data.tokens.DOC_MEDIAN", 30.0)
    sink = str(tmp_path / "ev.jsonl")
    cfg = tiny_solar_open2(telemetry=TelemetryConfig(events_path=sink))
    result = run_experiment(cfg, verbose=False)
    ds = build_experiment(cfg).dataset
    rows = [ds.x_train[ds.client_of_row == c] for c in range(4)]
    init = jax.tree.map(np.asarray, build_model(cfg.model)[0](
        jax.random.key(cfg.fed.init_seed)))
    want, ref_params = ref.fedavgm_rounds(
        init, rows, 1, ref_cfg(cfg.model),
        learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr)
    assert np.max(np.abs(np.stack(result.loss) - want)) <= 2e-5
    assert _gap(result.final_params, ref_params) <= 2e-5
    assert _gap(result.final_params, init) > 1e-3               # it moved
    events = [json.loads(line) for line in open(sink)]
    snapshot = [e for e in events if e["kind"] == "counters"][-1]["payload"]
    counted, gauges = snapshot["counters"], snapshot["gauges"]
    tokens = int((ds.x_train[:, 1] > 0).sum())
    assert counted["kda_positions"] == 10 * T * 3           # three KDA layers
    assert counted["kda_head_steps"] == tokens * 8 * 3
    assert 0.3 < counted["kda_steps_over_one"] / counted["kda_head_steps"] < 0.7
    assert 1.0 < gauges["kda_step_max"] < 2.0
    assert counted["moe_assignments_total"] == 4 * 4 * tokens   # four layers
    assert counted["moe_tokens_dropped"] == 0
    assert counted["lm_fused_attention_positions"] == 0     # a CPU


# ----------------------------------- the loss and every gradient, one step
def test_the_loss_and_every_gradient_are_the_references():
    """A row of three packed documents and padding, jittered gains, float32:
    the loss to 1e-5 and every leaf's gradient to 5e-5 of the leaf's largest
    entry (the order of the sums); the softmax layer leads and holds its
    gate."""
    params = seeded(TINY)
    assert kl.layer_kinds(TINY) == (("full", "experts"),) + (
        ("kda", "experts"),) * 3
    assert set(params["layers"][0]["mixer"]) == {"norm", "q", "k", "v",
                                                 "gate", "o"}
    rng = np.random.default_rng(0)
    task = build_task(TINY, build_model(TINY)[1], 128)
    row = jnp.asarray(packed_row(rng, (33, 41, 30)))
    grad = jax.jit(jax.value_and_grad(task.loss, has_aux=True))
    (loss, stats), g = grad(params, row[None], None, jnp.ones((1,)))
    with jax.default_matmul_precision("highest"):
        (want, sums), rg = jax.value_and_grad(
            lambda q: ref.mean_loss(q, row, ref_cfg(TINY)),
            has_aux=True)(params)
    assert abs(float(loss) - float(want)) <= 1e-5
    for ours, theirs in zip(("loss_sum", "count"), sums):
        np.testing.assert_allclose(float(stats[ours]), float(theirs),
                                   rtol=2e-6)
    assert float(stats["kda_restarts"]) == 3 * 3
    assert float(stats["kda_head_steps"]) == 104 * 8 * 3
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(rg)):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(b).max())
        if "router_bias" in name:       # no gradient reaches it
            assert float(jnp.abs(a).max()) == scale == 0.0
            continue
        assert scale > 1e-7, name                       # it is reached
        assert float(jnp.abs(a - b).max()) <= 5e-5 * scale + 1e-9, name


# ------------------------------------------------------------- the gate
def _softmax_layer(seed=5, positive=False):
    layer = seeded(TINY, seed)["layers"][0]["mixer"]
    h = jax.random.normal(jax.random.key(seed), (T, 48))
    segs = jnp.asarray([1] * 50 + [2] * 60 + [0] * 18, jnp.int32)
    return layer, (jnp.abs(h) + 1.0 if positive else h), segs


def test_the_gate_shut_half_open_and_left_out():
    """The gated layer is the reference's; with ``W_g`` zero the gate is a
    half everywhere and the output half the ungated layer's (the hybrid
    stack's function: the same layer without its ``gate`` leaf), to the bit;
    with the gate driven shut (a positive input, ``W_g`` = -100: every
    logit under -3,000) the mixer's output is zero; and a reference WITHOUT
    the gate lies a thousand tolerances from the program."""
    layer, h, segs = _softmax_layer()
    run = lambda layer, h=h: layers.attention_mixer(
        TINY, jnp.float32, h, layer, segs, eps=1e-5)[0]
    ours = run(layer)
    x = ref._rms(h, layer["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        theirs = ref.attention(layer, x, segs, ref_cfg(TINY))
        ungated = ref.attention(layer, x, segs,
                                {**ref_cfg(TINY), "use_gqa_gate": False})
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=0,
                               atol=1e-5)
    assert float(jnp.abs(ours - ungated).max()) > 1e-2
    bare = {k: v for k, v in layer.items() if k != "gate"}
    half = run({**layer, "gate": jnp.zeros_like(layer["gate"])})
    # the product with a half is exact, the output projection is linear
    np.testing.assert_allclose(np.asarray(2 * half), np.asarray(run(bare)),
                               rtol=0, atol=1e-6)
    _, positive, _ = _softmax_layer(positive=True)
    shut = run({**layer, "norm": jnp.ones_like(layer["norm"]),
                "gate": jnp.full_like(layer["gate"], -100.0)}, positive)
    assert float(jnp.abs(shut).max()) == 0.0
    assert float(jnp.abs(run(bare, positive)).max()) > 1e-2


def test_the_move_left_the_hybrid_and_the_delta_rule_presets_as_they_were():
    """The hybrid stack's ``*`` layer IS ``layers.attention_mixer`` (no copy
    in its module), and on a layer without a gate the function is the body
    it had there, to the bit; Kimi-Linear's full layer stays latent attention
    and its step stays under 1."""
    assert nemotron_h._MIXERS["attention"] is layers.attention_mixer
    assert not hasattr(nemotron_h, "attention_core")
    assert LANGUAGE_MODELS["solar_open2"] == LANGUAGE_MODELS["kimi_linear"]
    hybrid = dataclasses.replace(
        get_preset("nemotron-h-30b-a3b-l9").model, hidden_size=48,
        num_attention_heads=8, num_key_value_heads=2, head_dim=16)
    layer, h, segs = _softmax_layer()
    layer = {k: v for k, v in layer.items() if k != "gate"}
    layer["k"], layer["v"] = layer["k"][:, :32], layer["v"][:, :32]

    def as_it_was(cfg, compute_dtype, h, layer, segs):
        t = h.shape[0]
        heads, kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        cast = lambda arr: arr.astype(compute_dtype)
        x = cast(layers.rms_norm(h, layer["norm"], cfg.layer_norm_epsilon))
        q = layers._mm(x, cast(layer["q"])).reshape(t, heads, hd)
        k, v = (jnp.repeat(layers._mm(x, cast(layer[name])).reshape(t, kv, hd),
                           heads // kv, axis=1) for name in ("k", "v"))
        ctx = attention_core(q, k, v, segs, compute_dtype)
        return layers._mm(cast(ctx.reshape(t, heads * hd)), cast(layer["o"]))

    for dtype in (jnp.float32, jnp.bfloat16):
        ours, stats = layers.attention_mixer(hybrid, dtype, h, layer, segs)
        assert stats == {}
        assert bool((ours == as_it_was(hybrid, dtype, h, layer, segs)).all())
    kimi = get_preset("kimi-linear-48b-a3b-l5").model
    assert not (kimi.kda_allow_neg_eigval or kimi.use_gqa_gate
                or kimi.gqa_layers)
    assert kl.layer_kinds(kimi)[3] == ("full", "experts")
    shapes = jax.eval_shape(build_model(kimi)[0], jax.random.key(0))
    assert "kv_a" in shapes["layers"][3]["mixer"]


def test_the_steps_factor_is_two_and_it_matters(monkeypatch):
    """``kda_allow_neg_eigval``: the program's KDA mixer is the reference's
    with ``beta = 2 sigmoid`` to 1e-5 and a hundred tolerances from the
    mixer with ``beta = sigmoid``; about half the steps are over 1 at the
    start, none without the factor. In float32 the comparison tells a lower
    precision inside the recurrence too: the reference with its state, or
    with its decays ``exp(g)``, rounded to bfloat16 lies more than ten
    tolerances from the program."""
    layer = seeded(TINY, 5)["layers"][1]["mixer"]
    h = jax.random.normal(jax.random.key(5), (T, 48))
    segs = jnp.asarray([1] * 37 + [2] * 50 + [3] * 30 + [0] * 11, jnp.int32)
    ours, stats = kl.kda_mixer(TINY, jnp.float32, h, layer, segs)
    x = ref._rms(h, layer["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        theirs = ref.kda(layer, x, segs, ref_cfg(TINY))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=0,
                               atol=1e-5)
    plain, without = kl.kda_mixer(
        dataclasses.replace(TINY, kda_allow_neg_eigval=False), jnp.float32, h,
        layer, segs)
    assert float(jnp.abs(ours - plain).max()) > 1e-3
    assert float(stats["kda_head_steps"]) == 117 * 8
    assert 0.3 * 117 * 8 < float(stats["kda_steps_over_one"]) < 0.7 * 117 * 8
    assert 1.0 < float(stats["kda_step_max"]) < 2.0
    assert float(without["kda_steps_over_one"]) == 0.0
    assert float(without["kda_step_max"]) < 1.0

    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

    def rounded_state(state, tok):
        state, o = token(state, tok)
        return rounded(state), o

    def rounded_decays(state, tok):
        q, k, v, g, beta, start = tok
        state = jnp.where(start, 0.0, state) * rounded(jnp.exp(g))[:, :, None]
        return token(state, (q, k, v, jnp.zeros_like(g), beta,
                             jnp.zeros_like(start)))

    token = ref.base.kda_token
    for changed in (rounded_state, rounded_decays):
        monkeypatch.setattr(ref.base, "kda_token", changed)
        with jax.default_matmul_precision("highest"):
            other = ref.kda(layer, x, segs, ref_cfg(TINY))
        assert float(jnp.abs(ours - other).max()) > 1e-4, changed.__name__


# ---------------------------------------------------- the kinds of layers
def test_layer_kinds_from_the_published_lists_and_what_the_registry_refuses():
    """Solar-Open2's ``gqa_layers`` is 0-based and names the softmax layers
    alone; Kimi-Linear's two lists are 1-based and name every layer."""
    twelve = dataclasses.replace(TINY, num_hidden_layers=12,
                                 gqa_layers=(0, 4, 8))
    kinds = [mixer for mixer, _ in kl.layer_kinds(twelve)]
    assert kinds == ["full", "kda", "kda", "kda"] * 3
    assert {ffn for _, ffn in kl.layer_kinds(twelve)} == {"experts"}
    # the list decides the full layer's kind, not the model's name: the same
    # stack under the other name, and a stack of KDA layers alone by the
    # 1-based lists under this one
    renamed = dataclasses.replace(TINY, kind="kimi_linear")
    assert kl.layer_kinds(renamed) == kl.layer_kinds(TINY)
    shapes = jax.eval_shape(build_model(renamed)[0], jax.random.key(0))
    assert "gate" in shapes["layers"][0]["mixer"]
    none = dataclasses.replace(TINY, gqa_layers=(), kda_layers=(1, 2, 3, 4),
                               full_attn_layers=())
    assert [m for m, _ in kl.layer_kinds(none)] == ["kda"] * 4
    # where gqa_layers names the layers the two 1-based lists are not read
    assert kl.layer_kinds(dataclasses.replace(
        TINY, kda_layers=(9,), full_attn_layers=(7,))) == kl.layer_kinds(TINY)
    for bad in ((4,), (0, 0), (-1,)):
        with pytest.raises(ValueError, match="0-based, as Solar-Open2"):
            build_model(dataclasses.replace(TINY, gqa_layers=bad))
    with pytest.raises(ValueError, match="1-based, as Kimi-Linear"):
        build_model(dataclasses.replace(TINY, gqa_layers=(),
                                        num_hidden_layers=5))
    with pytest.raises(ValueError, match="do not divide over"):
        build_model(dataclasses.replace(TINY, num_key_value_heads=3))
    with pytest.raises(ValueError, match="not among the 16"):
        build_model(dataclasses.replace(TINY, first_expert=14))
    ungated = build_model(dataclasses.replace(TINY, use_gqa_gate=False))[0]
    shapes = jax.eval_shape(ungated, jax.random.key(0))
    assert "gate" not in shapes["layers"][0]["mixer"]


# ------------------------------------------------------ the shares add up
def _columns(layer, names, at, width):
    return {**layer, **{n: layer[n][:, at:at + width] for n in names}}


def test_the_head_shares_and_the_expert_shares_add_up_to_the_uncut_layer():
    """One layer of each kind at 8 heads / 4 key-value heads / 16 experts.
    Four chips share a mixer by heads (2 query or KDA heads and 1 key-value
    head each): a share holds its heads' columns of the input projections,
    convolutions, decay, step, gate and head norm and its heads' ROWS of
    ``W_o``, and the whole-held matrices (the pre-norm, ``f_a``, ``g_a``)
    are each chip's alike; the four partial outputs add up to the uncut
    reference's mixer, nothing counted twice because ``W_o`` is cut by
    rows. Four chips share the expert layer by experts: the four partial
    results, with the shared expert (which every chip computes alike)
    counted once, are the uncut reference's."""
    params = seeded(TINY, 7)
    h = jax.random.normal(jax.random.key(8), (T, 48))
    segs = jnp.asarray([1] * 60 + [2] * 68, jnp.int32)
    quarter = dataclasses.replace(TINY, num_attention_heads=2,
                                  num_key_value_heads=1, kda_num_heads=2)

    # the softmax layer: query heads 2i, 2i + 1 read key-value head i
    layer = params["layers"][0]["mixer"]
    x = ref._rms(h, layer["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        uncut = ref.attention(layer, x, segs, ref_cfg(TINY))
    total = 0.0
    for i in range(4):
        part = _columns(layer, ("q", "gate"), 32 * i, 32)
        part = _columns(part, ("k", "v"), 16 * i, 16)
        part["o"] = layer["o"][32 * i:32 * (i + 1)]
        out, _ = layers.attention_mixer(quarter, jnp.float32, h, part, segs,
                                        eps=1e-5)
        with jax.default_matmul_precision("highest"):
            want = ref.attention(part, x, segs, ref_cfg(quarter))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=0,
                                   atol=1e-5)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=0,
                               atol=2e-5)
    assert float(jnp.abs(uncut).max()) > 0.02

    # a KDA mixer: heads 2i, 2i + 1
    layer = params["layers"][1]["mixer"]
    x = ref._rms(h, layer["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        uncut = ref.kda(layer, x, segs, ref_cfg(TINY))
    total = 0.0
    for i in range(4):
        part = _columns(layer, ("q_proj", "k_proj", "v_proj", "q_conv",
                                "k_conv", "v_conv", "f_b", "g_b"), 32 * i, 32)
        part.update(b_proj=layer["b_proj"][:, 2 * i:2 * i + 2],
                    A_log=layer["A_log"][2 * i:2 * i + 2],
                    dt_bias=layer["dt_bias"][32 * i:32 * (i + 1)],
                    g_bias=layer["g_bias"][32 * i:32 * (i + 1)],
                    o_proj=layer["o_proj"][32 * i:32 * (i + 1)])
        # held whole on every chip
        assert part["f_a"] is layer["f_a"] and part["g_a"] is layer["g_a"]
        out, _ = kl.kda_mixer(quarter, jnp.float32, h, part, segs)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=0,
                               atol=2e-5)
    assert float(jnp.abs(uncut).max()) > 0.02

    # the expert layer: experts 4i .. 4i + 3, the shared expert once
    whole = dataclasses.replace(TINY, experts_held=0, first_expert=0)
    key = jax.random.key(9)
    count = iter(range(100))
    layer = layers._ffn_init(
        "experts", whole, lambda *s: 0.3 * jax.random.normal(
            jax.random.fold_in(key, next(count)), s),
        lambda *s: jnp.ones(s))
    x = ref._rms(h, layer["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        uncut = ref.experts(layer, x, ref_cfg(whole))
        shared = ref.base.gated(x, layer["shared_gate"], layer["shared_up"],
                                layer["shared_down"])
    total, held_sum = 0.0, 0.0
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(TINY, experts_held=4, first_expert=first)
        part = {**layer, **{name: layer[name][first:first + 4]
                            for name in ("gate", "up", "down")}}
        out, stats = layers.experts_mixer(share, jnp.float32, h, part, segs,
                                          eps=1e-5)
        total, held_sum = total + out, held_sum + stats["assignments_held"]
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(uncut), rtol=0, atol=1e-4)
    assert float(held_sum) == 4 * T          # every assignment, exactly once
    assert float(jnp.abs(uncut - shared).max()) > 0.1


# ------------------------------------------------ the published widths
def test_the_parameter_count_of_the_published_configuration():
    """The program's count, the configuration file's own sum and
    ``flops_solar_open2.params`` agree, part by part (ISSUE 47's
    arithmetic), and the file's share is the preset's."""
    from perfbench.drivers import train_solar_open2

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs",
                           "solar-open2-250b-l4-fed8.json")) as fh:
        conf = json.load(fh)
    preset = get_preset("solar-open2-250b-l4").model
    fields = train_solar_open2.model_fields(conf)
    assert {k: getattr(preset, k) for k in fields} == fields
    shapes = jax.eval_shape(build_model(preset)[0], jax.random.key(0))
    size = lambda tree: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
    counted, stated = flops_solar_open2.params(fields), conf["parameter_sum"]
    found = shapes["layers"]
    assert kl.layer_kinds(preset) == (("full", "experts"),) + (
        ("kda", "experts"),) * 3
    assert (size(found[0]["mixer"]) - 4096 == counted["gqa_mixer"]
            == stated["gqa_mixer"] == 27_262_976)
    assert all(size(found[i]["mixer"]) - 4096 == counted["kda_mixer"]
               == stated["kda_mixer"] == 35_221_648 for i in (1, 2, 3))
    assert all(size(found[i]["ffn"]) - 4096 == counted["feed_forward"]
               == stated["feed_forward"] == 142_868_800 for i in range(4))
    assert size(shapes["embed"]) == size(shapes["head"]) == 100_663_296
    assert (size(shapes) == counted["total"] == conf["parameters"]
            == 905_766_576)
    assert conf["memory"]["engine_bytes"] == 12 * 905_766_576 == 10_869_198_912
    published, layout = conf["published"], conf["layout"]
    assert published["num_attention_heads"] == (
        layout["chips_sharing_a_mixer"] * conf["num_attention_heads"])
    assert published["n_routed_experts"] == (
        layout["chips_sharing_a_layer"] * conf["n_routed_experts"])
    assert published["vocab_size"] == (
        layout["vocabulary_cut_in"] * conf["vocab_size"])
