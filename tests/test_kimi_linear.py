"""The Kimi-Linear stack, fedtpu.models.kimi_linear, against its plain
reference (perfbench/reference_kimi_linear.py): the chunked delta-rule
recurrence against the token-by-token one, values and every gradient, over
chunk sizes, documents that start inside a chunk and decays at which
``exp(-G)`` overflows; the triangular inverse; two federated rounds through
``run_experiment`` (every client's loss, every global parameter, the
counters); the loss and every gradient on packed rows; what gives the
comparison teeth (no delta term, no decay, the gates off); latent attention
without a bottleneck and without positions; the shares of an expert layer
adding up to the uncut layer; the parameter count of the published
configuration; the scopes; what the registry refuses."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.config import ModelConfig, TelemetryConfig, get_preset
from fedtpu.models import kimi_linear as kl
from fedtpu.models import layers
from fedtpu.models.registry import build_model
from fedtpu.ops import kda_scan, ssm_passes
from fedtpu.orchestration.loop import build_experiment, run_experiment
from fedtpu.training.task import build_task
from perfbench import flops_kimi_linear, reference_kimi_linear as ref

T = 128
TINY = ModelConfig(
    kind="kimi_linear", hidden_size=48, num_attention_heads=4,
    num_hidden_layers=5, kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
    kda_num_heads=4, kda_head_dim=16, short_conv_kernel_size=4,
    first_k_dense_replace=1, intermediate_size=96, q_lora_rank=None,
    mla_use_nope=True, rope_scaling_factor=1.0, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=16, experts_held=4, first_expert=4,
    moe_intermediate_size=24, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=2.446, rms_norm_eps=1e-5, vocab_size=128)


def ref_cfg(cfg):
    """The reference's dictionary of a ModelConfig, under the published
    config's own key names."""
    return {"num_attention_heads": cfg.num_attention_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "num_experts_per_token": cfg.num_experts_per_tok,
            "moe_renormalize": cfg.norm_topk_prob,
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


def tiny_kimi_linear(rounds=2, **run):
    cfg = get_preset("kimi-linear-48b-a3b-l5")
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


# ------------------------------- (a) the chunked form is the recurrence
def _scan_inputs(segs, strength, heads=2, d=8, seed=0, bias=0.0, step=1.0):
    """Normalised q and k (drawn around ``bias``: at 3 two keys' cosine is
    0.9, as after a SiLU), a never-positive log-decay of up to ``strength`` a
    token and channel, steps in (0, ``step``): at ``step`` 2 (Solar-Open2's
    ``beta = 2 sigmoid``) drawn a logit higher, so that three in four are
    over 1, where ``I - beta k k^T`` has a negative eigenvalue."""
    t = len(segs)
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    g = -strength * jnp.asarray(rng.uniform(0, 1, (t, heads, d)), jnp.float32)
    return (unit(f(t, heads, d) + bias) * d ** -0.5,
            unit(f(t, heads, d) + bias), f(t, heads, d), g,
            step * jax.nn.sigmoid(f(t, heads) + bias + (step > 1)),
            f(t, heads, d))


SEVERAL = [1] * 37 + [2] * 50 + [3] * 30 + [0] * 11     # none starts a chunk
ONE = [1] * T


@pytest.mark.parametrize("segs,chunk,sub,strength,bias,step", [
    (SEVERAL, 64, 16, 0.1, 0, 1), (SEVERAL, 32, 8, 0.1, 0, 1),
    (SEVERAL, 16, 16, 0.1, 0, 1), (SEVERAL, 128, 16, 0.1, 0, 1),
    (ONE, 64, 16, 0.1, 0, 1), (SEVERAL, 64, 16, 4.0, 0, 1),
    (ONE, 64, 16, 4.0, 0, 1), (ONE, 64, 16, 0.01, 3, 1),
    (SEVERAL, 32, 8, 0.1, 0, 2), (SEVERAL, 64, 16, 4.0, 0, 2),
    (ONE, 64, 16, 0.01, 3, 2)],
    ids=["chunk64", "chunk32-sub8", "chunk16-one-sub-chunk", "one-chunk",
         "one-document", "overflowing-decay", "overflowing-one-document",
         "keys-alike-and-slow-decay", "steps-to-two-restarts-inside-chunks",
         "steps-to-two-overflowing-decay",
         "steps-to-two-keys-alike-and-slow-decay"])
def test_the_chunked_recurrence_is_the_token_by_token_one(segs, chunk, sub,
                                                          strength, bias,
                                                          step):
    """Values and the gradient of every input, float32: the order of the sums
    differs and nothing else (the largest gap seen is 7e-6 on a gradient of
    4.5). At ``strength`` 4 a chunk's cumulative log-decay passes -88 many
    times over (-128 on average over 64 tokens), where ``exp(-G)`` is
    infinite in float32: every value and gradient is finite and the
    recurrence's. With keys alike (cosine 0.9), steps near 1 and hardly any
    decay the chunk's triangular matrix has entries near 1 below its
    diagonal: the case in which a product of powers for its inverse read
    1e28 (the chip's first run of the cell was not a number). With steps
    drawn over (0, 2) and mostly above 1 (Solar-Open2's
    ``kda_allow_neg_eigval``) the triangular matrix's entries are twice as
    large and the state flips sign along a key: the values' tolerance is
    twice the other cases', of the largest value where that is over 1
    (corrections twice as large: 2.4e-6 seen on values under 1.7; with keys
    alike and hardly any decay nothing damps an error, the outputs reach 7
    and the chunked form lies 1.9e-5 from the recurrence, which itself lies
    2e-6 from a float64 one) and the gradients' the same, with keys alike too (entries near 1.8 under the diagonal, every factor
    ``1 - beta`` still inside the unit circle)."""
    segs = jnp.asarray(segs, jnp.int32)
    *inputs, weigh = _scan_inputs(segs, strength, bias=bias, step=step)
    if step > 1:
        over = float((inputs[4] > 1).mean())
        assert over > 0.6 and float(inputs[4].max()) > 1.9, over
    run, starts = ssm_passes.document_runs(segs)
    if strength > 1:
        deepest = np.asarray(inputs[3]).reshape(-1, chunk, 2, 8).sum(1).min()
        with np.errstate(over="ignore"):
            assert deepest < -100 and np.isinf(np.exp(np.float32(-deepest)))
    chunked = lambda *a: kda_scan.kda_scan(*a, run, chunk, jnp.float32, sub)
    plain = lambda *a: ref.kda_recurrence(*a, starts)
    total = lambda fn: lambda *a: (fn(*a) * weigh).sum()
    want = np.asarray(plain(*inputs))
    np.testing.assert_allclose(
        np.asarray(chunked(*inputs)), want, rtol=0,
        atol=2e-6 if step == 1 else 4e-6 * max(float(np.abs(want).max()), 1.0))
    ours = jax.grad(total(chunked), argnums=range(5))(*inputs)
    theirs = jax.grad(total(plain), argnums=range(5))(*inputs)
    for name, a, b in zip("qkvgb", ours, theirs):
        assert bool(jnp.isfinite(a).all()), name
        scale = float(jnp.abs(b).max())
        assert scale > 0.1, name                            # it is reached
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-5 * max(scale, 1.0), err_msg=name)


def test_a_document_packed_behind_another_scans_as_it_does_alone():
    """State, decay and the intra-chunk matrix restart at a document's first
    token wherever it falls: the second document's outputs are those of the
    same tokens in a row of their own."""
    segs = jnp.asarray([1] * 37 + [2] * 91, jnp.int32)
    *inputs, _ = _scan_inputs(segs, 0.5, seed=1)
    both = kda_scan.kda_scan(*inputs, ssm_passes.document_runs(segs)[0], 64,
                             jnp.float32)
    alone = kda_scan.kda_scan(*(jnp.pad(a[37:], ((0, 37),) + ((0, 0),) * (a.ndim - 1))
                          for a in inputs),
                        ssm_passes.document_runs(jnp.asarray([1] * 91 + [0] * 37))[0],
                        64, jnp.float32)
    np.testing.assert_allclose(np.asarray(both[37:]), np.asarray(alone[:91]),
                               rtol=0, atol=2e-6)


def test_bfloat16_products_stay_near_the_float32_ones():
    segs = jnp.asarray(SEVERAL, jnp.int32)
    *inputs, _ = _scan_inputs(segs, 0.3, seed=2)
    run = ssm_passes.document_runs(segs)[0]
    exact = kda_scan.kda_scan(*inputs, run, 64, jnp.float32)
    rounded = kda_scan.kda_scan(*inputs, run, 64, jnp.bfloat16)
    assert rounded.dtype == jnp.float32
    assert 1e-6 < float(jnp.abs(exact - rounded).max()) < 0.03 * float(
        jnp.abs(exact).max())


def test_the_inverse_of_a_unit_lower_triangular_matrix_and_its_gradient():
    rng = np.random.default_rng(0)
    for c in (1, 2, 16, 48, 64):
        # entries of one sign near a half: the powers of ``low`` reach 1e9
        # before they cancel, and the substitution does not form them
        low = jnp.asarray(np.tril(0.5 + rng.normal(size=(3, c, c)) * 0.1, -1),
                          jnp.float32)
        inv = kda_scan.unit_lower_inverse(low)
        np.testing.assert_allclose(
            np.asarray(inv @ (jnp.eye(c) + low)),
            np.broadcast_to(np.eye(c), (3, c, c)), rtol=0, atol=2e-5)
        weigh = jnp.asarray(rng.normal(size=(3, c, c)), jnp.float32)
        ours = jax.grad(lambda m: (kda_scan.unit_lower_inverse(m) * weigh).sum())(low)
        theirs = jax.grad(lambda m: (jnp.linalg.inv(jnp.eye(c) + m)
                                     * weigh).sum())(low)
        scale = max(float(jnp.abs(theirs).max()), 1.0)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                                   rtol=0, atol=1e-4 * scale)


# --------------------------------------- (b) the normal path, two rounds
def test_two_rounds_through_run_experiment_match_the_references_fedavgm(
        tmp_path, monkeypatch):
    """float32 on both sides: the gaps are the order of the sums, so 2e-5 on
    losses near 4.9 and on parameters that moved by 1e-2, as the other
    language models' rounds. Rows of 128 tokens of short documents: a
    document's state crosses a chunk's edge and others start inside one."""
    monkeypatch.setattr("fedtpu.data.tokens.DOC_MEDIAN", 30.0)
    sink = str(tmp_path / "ev.jsonl")
    cfg = tiny_kimi_linear(telemetry=TelemetryConfig(events_path=sink))
    result = run_experiment(cfg, verbose=False)
    ds = build_experiment(cfg).dataset
    rows = [ds.x_train[ds.client_of_row == c] for c in range(4)]
    assert sorted(len(r) for r in rows) == [1, 2, 3, 4]         # size skew
    init = jax.tree.map(np.asarray, build_model(cfg.model)[0](
        jax.random.key(cfg.fed.init_seed)))
    want, ref_params = ref.fedavgm_rounds(
        init, rows, 2, ref_cfg(cfg.model),
        learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr)
    assert np.max(np.abs(np.stack(result.loss) - want)) <= 2e-5
    assert _gap(result.final_params, ref_params) <= 2e-5
    assert _gap(result.final_params, init) > 1e-3               # it moved
    events = [json.loads(line) for line in open(sink)]
    snapshot = [e for e in events if e["kind"] == "counters"][-1]["payload"]
    counted, gauges = snapshot["counters"], snapshot["gauges"]
    segs = ds.x_train[:, 1]
    tokens = int((segs > 0).sum())
    starts = int(((segs > 0) & (np.pad(segs, ((0, 0), (1, 0)))[:, :-1]
                                != segs)).sum())
    assert counted["moe_assignments_total"] == 2 * 4 * 4 * tokens
    assert 0 < counted["moe_assignments_held"] < counted["moe_assignments_total"]
    assert counted["moe_tokens_dropped"] == 0
    assert counted["stateless_client_steps"] == 2 * 10
    assert counted["kda_positions"] == 2 * 10 * T * 4       # four KDA layers
    assert counted["kda_document_restarts"] == 2 * 4 * starts
    assert starts > 20                                      # several a row
    assert counted["lm_fused_attention_positions"] == 0     # a CPU
    # the mean over a round's steps of a step's deepest chunk: negative, and
    # at this start (|g| under 1.6 a token) within 64 tokens' worth
    assert -64 * 1.7 < gauges["kda_log_decay_min"] < -1.0


# ----------------------------------- the loss and every gradient, one step
def test_the_loss_and_every_gradient_are_the_references():
    """Rows of two and three packed documents and padding, jittered gains,
    float32: the loss to 1e-5 and every leaf's gradient to 5e-5 of the
    leaf's largest entry (the order of the sums)."""
    params = seeded(TINY)
    rng = np.random.default_rng(0)
    task = build_task(TINY, build_model(TINY)[1], 128)
    grad = jax.jit(jax.value_and_grad(task.loss, has_aux=True))
    for lengths in ((50, 70), (33, 41, 30)):
        row = jnp.asarray(packed_row(rng, lengths))
        (loss, stats), g = grad(params, row[None], None, jnp.ones((1,)))
        with jax.default_matmul_precision("highest"):
            (want, sums), rg = jax.value_and_grad(
                lambda q: ref.mean_loss(q, row, ref_cfg(TINY)),
                has_aux=True)(params)
        assert abs(float(loss) - float(want)) <= 1e-5
        for ours, theirs in zip(("loss_sum", "count"), sums):
            np.testing.assert_allclose(float(stats[ours]), float(theirs),
                                       rtol=2e-6)
        assert float(stats["kda_restarts"]) == 4 * len(lengths)
        for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                                jax.tree.leaves(rg)):
            name = jax.tree_util.keystr(path)
            scale = float(jnp.abs(b).max())
            if "router_bias" in name:       # no gradient reaches it
                assert float(jnp.abs(a).max()) == scale == 0.0
                continue
            assert scale > 1e-7, name                       # it is reached
            assert float(jnp.abs(a - b).max()) <= 5e-5 * scale + 1e-9, name


# --------------------------- (c) what gives the comparison its teeth
def _mixer_and_input(seed=5):
    layer = seeded(TINY, seed)["layers"][1]["mixer"]
    h = jax.random.normal(jax.random.key(seed), (T, 48))
    return layer, h, jnp.asarray(SEVERAL, jnp.int32)


def test_the_mixer_is_the_references_and_the_delta_term_matters(monkeypatch):
    """The program's KDA mixer is the reference's to 1e-5; the reference
    WITHOUT the delta term (``S += beta k v^T``, a gated linear attention)
    lies a thousand tolerances away, and the reference with its state
    rounded to bfloat16 after every token more than ten: in float32 the
    comparison tells both (on the chip, beside bfloat16 inputs to every
    matmul, it tells the first alone: PERF.md section 6, PR 39)."""
    layer, h, segs = _mixer_and_input()
    ours, stats = kl.kda_mixer(TINY, jnp.float32, h, layer, segs)
    x = ref._rms(h, layer["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        theirs = ref.kda(layer, x, segs, ref_cfg(TINY))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=0,
                               atol=1e-5)
    assert float(stats["kda_restarts"]) == 3

    def no_delta(state, token):
        q, k, v, g, beta, start = token
        state = jnp.where(start, 0.0, state) * jnp.exp(g)[:, :, None]
        state = state + k[:, :, None] * (beta[:, None] * v)[:, None, :]
        return state, (state * q[:, :, None]).sum(axis=1)

    token = ref.kda_token

    def rounded_state(state, tok):
        state, o = token(state, tok)
        return state.astype(jnp.bfloat16).astype(jnp.float32), o

    for changed, least in ((no_delta, 1e-2), (rounded_state, 1e-4)):
        monkeypatch.setattr(ref, "kda_token", changed)
        with jax.default_matmul_precision("highest"):
            other = ref.kda(layer, x, segs, ref_cfg(TINY))
        assert float(jnp.abs(ours - other).max()) > least, changed.__name__


@pytest.mark.parametrize("off", ["decay", "step", "output-gate", "convolution"])
def test_each_gate_of_the_mixer_matters(off):
    """At the rehearsal's size and the start the program draws: without the
    decay (``A_log`` at -inf: every ``alpha`` 1), with the step held at a
    half (``W_b`` zero), the output gate held at a half (its projection and
    bias zero) or the convolution reading its own position alone, the
    mixer's output moves by more than a hundred times the comparison's
    tolerance (1e-5)."""
    layer, h, segs = _mixer_and_input()
    zero = lambda *names: {n: jnp.zeros_like(layer[n]) for n in names}
    own_tap = jnp.zeros_like(layer["q_conv"]).at[-1].set(1.0)
    changed = {
        "decay": {"A_log": jnp.full_like(layer["A_log"], -jnp.inf)},
        "step": zero("b_proj"), "output-gate": zero("g_b", "g_bias"),
        "convolution": {n: own_tap for n in ("q_conv", "k_conv", "v_conv")},
    }[off]
    full, _ = kl.kda_mixer(TINY, jnp.float32, h, layer, segs)
    without, _ = kl.kda_mixer(TINY, jnp.float32, h, {**layer, **changed}, segs)
    assert bool(jnp.isfinite(without).all())
    assert float(jnp.abs(full - without).max()) > 1e-3


# -------------------------------------------------- (d) latent attention
def test_latent_attention_without_bottleneck_or_positions_is_the_references():
    """``q_lora_rank`` None and ``mla_use_nope``: one query projection, the
    rotary columns kept and nothing rotated; and a document packed behind
    another attends as it does alone (no positions to restart, the mask
    alone)."""
    layer = seeded(TINY, 3)["layers"][3]["mixer"]
    assert set(layer) == {"norm", "q", "kv_a", "kv_a_norm", "kv_b", "o"}
    h = jax.random.normal(jax.random.key(4), (T, 48))
    segs = jnp.asarray([1] * 50 + [2] * 60 + [0] * 18, jnp.int32)
    ours = layers.latent_attention(TINY, jnp.float32, h, layer, segs, None)
    with jax.default_matmul_precision("highest"):
        theirs = ref.attention(layer, ref._rms(h, layer["norm"], 1e-5), segs,
                               ref_cfg(TINY))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs), rtol=0,
                               atol=1e-5)
    alone = layers.latent_attention(
        TINY, jnp.float32, jnp.pad(h[50:110], ((0, 68), (0, 0))), layer,
        jnp.asarray([1] * 60 + [0] * 68, jnp.int32), None)
    np.testing.assert_allclose(np.asarray(ours[50:110]),
                               np.asarray(alone[:60]), rtol=0, atol=1e-5)
    # with positions the same layer gives another result (at scores large
    # enough to tell): nothing rotates here
    loud = {**layer, "q": 8 * layer["q"], "kv_a": 8 * layer["kv_a"]}
    plain, rotated = (layers.latent_attention(
        dataclasses.replace(TINY, mla_use_nope=nope), jnp.float32, h, loud,
        segs, jnp.arange(T)) for nope in (True, False))
    assert float(jnp.abs(plain - rotated).max()) > 1e-3


# ------------------------------------------------ (e) the shares add up
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """16 routed gated experts in 4 shares of 4 (top 4 of sigmoid scores,
    renormalised, times 2.446): the four partial results, with the shared
    expert (which every chip computes alike) counted once, are the uncut
    reference layer's."""
    whole = dataclasses.replace(TINY, experts_held=0, first_expert=0)
    key = jax.random.key(7)
    count = iter(range(100))
    layer = layers._ffn_init(
        "experts", whole, lambda *s: 0.3 * jax.random.normal(
            jax.random.fold_in(key, next(count)), s),
        lambda *s: jnp.ones(s))
    layer["norm"] = layer["norm"] + 0.1 * jax.random.normal(key, (48,))
    h = jax.random.normal(jax.random.key(8), (T, 48))
    segs = jnp.asarray([1] * 60 + [2] * 68, jnp.int32)
    x = ref._rms(h, layer["norm"], 1e-5)
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
                                      eps=1e-5)
        total, held_sum = total + out, held_sum + stats["assignments_held"]
        with jax.default_matmul_precision("highest"):
            want = ref.experts(part, x, ref_cfg(share))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=0, atol=5e-5)
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(uncut), rtol=0, atol=1e-4)
    assert float(held_sum) == 4 * T          # every assignment, exactly once
    assert float(jnp.abs(uncut - shared).max()) > 0.1    # the routed part is there


# ------------------------------------------------ (f) the published widths
def test_the_parameter_count_of_the_published_configuration():
    """The program's count, the configuration file's and
    ``flops_kimi_linear.params`` agree, part by part (ISSUE 39's
    arithmetic)."""
    from perfbench.drivers import train_kimi_linear

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs",
                           "kimi-linear-48b-a3b-l5-fed8.json")) as fh:
        conf = json.load(fh)
    preset = get_preset("kimi-linear-48b-a3b-l5").model
    fields = train_kimi_linear.model_fields(conf)
    assert {k: getattr(preset, k) for k in fields} == fields
    shapes = jax.eval_shape(build_model(preset)[0], jax.random.key(0))
    size = lambda tree: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
    counted = flops_kimi_linear.params(fields)
    layers = shapes["layers"]
    assert kl.layer_kinds(preset) == (
        ("kda", "dense"), ("kda", "experts"), ("kda", "experts"),
        ("full", "experts"), ("kda", "experts"))
    assert size(layers[0]["mixer"]) - 2304 == counted["kda_mixer"] == 39_518_368
    assert size(layers[3]["mixer"]) - 2304 == counted["full_mixer"] == 29_114_880
    assert size(layers[0]) == counted["kda_dense_layer"] == 103_223_968
    assert all(size(layers[i]) == counted["kda_experts_layer"] == 103_814_048
               for i in (1, 2, 4))
    assert size(layers[3]) == counted["full_experts_layer"] == 93_410_560
    assert size(shapes["embed"]) == size(shapes["head"]) == 47_185_920
    assert size(shapes) == counted["total"] == conf["parameters"] == 602_450_816
    assert conf["memory"]["engine_bytes"] == 12 * 602_450_816


def test_the_start_of_the_decay_is_the_one_the_file_assumes():
    """``A_log = log U(1, 16)``, ``dt_bias`` the inverse softplus of a
    log-uniform step in [0.001, 0.1], the convolutions uniform within
    ``kernel^-1/2``, the output gate's bias zero: so a token's log-decay
    lies in (-1.6, 0)."""
    mixer = build_model(TINY)[0](jax.random.key(1))["layers"][0]["mixer"]
    a, dt = np.exp(np.asarray(mixer["A_log"])), jax.nn.softplus(mixer["dt_bias"])
    assert a.shape == (4,) and 1.0 <= a.min() and a.max() <= 16.0
    assert dt.shape == (64,) and 0.001 <= float(dt.min()) and float(dt.max()) <= 0.1001
    for name in ("q_conv", "k_conv", "v_conv"):
        assert mixer[name].shape == (4, 64)
        assert float(jnp.abs(mixer[name]).max()) <= 0.5
    assert float(jnp.abs(mixer["g_bias"]).max()) == 0.0
    assert mixer["f_a"].shape == (48, 16) and mixer["g_b"].shape == (16, 64)


def test_the_scopes_of_a_tiny_round_name_this_stacks_layers_and_pieces():
    """One walk of the compiled round's text: the two layers and the four
    pieces this stack brings are there, the pieces inside ``kda`` and the
    scan's operations the scan's own."""
    from fedtpu.analysis.program import program_scopes
    from fedtpu.orchestration import loop
    from fedtpu.parallel.round import (LAYERS, PIECES, RECOMPUTE,
                                       SERVER_UPDATE, SGD_PASS, STAGES)
    exp = build_experiment(tiny_kimi_linear())
    text = exp.make_step(1).lower(exp.state, exp.batch).compile().as_text()
    walk = program_scopes(
        text, STAGES + (loop.STATE_CHECK,), layers=LAYERS, pieces=PIECES,
        update=(SGD_PASS, SERVER_UPDATE), recompute=(RECOMPUTE,))
    layers, pieces = walk["layers"], walk["pieces"]
    assert {"kda", "kda_scan", "attention", "dense_mlp", "shared_expert",
            "router", "expert_dispatch", "experts", "lm_head_loss",
            "server_update", "embed"} <= set(layers.values())
    assert {"kda_in_proj", "kda_conv", "kda_gates", "kda_out_proj",
            "attn_core", "attn_latent", "sgd_pass"} <= set(pieces.values())
    found = [layers[k] for k, piece in pieces.items()
             if piece.startswith("kda_") and k in layers]
    assert found and found.count("kda") >= 0.95 * len(found)
    passes = {walk["passes"].get(k, "forward") for k, layer in layers.items()
              if layer == "kda_scan"}
    assert {"forward", "recompute", "backward"} <= passes


def test_the_recurrences_kernels_keep_the_scans_scope_in_a_tiny_round(
        monkeypatch):
    """The rule between the bodies told yes and the kernels interpreted
    (``jax.checkpoint`` a pass-through: the interpreter's callbacks cannot
    stand under it), a tiny round LOWERED names ``kda_scan_forward`` and
    ``kda_scan_backward`` on its operations' name stacks, each under
    ``kda/kda_scan`` and so the layer ``kda_scan`` (what ``kl_kda_scan_ms``
    reads), the second in the backward pass. (The compiled round at
    published widths holds the same of the Mosaic calls themselves:
    ``tests/test_aot_tpu_compile.py``.)"""
    import re

    from jax.experimental.pallas import tpu as pltpu

    from fedtpu.analysis.program import BACKWARD, _pass_of, _stage_of
    from fedtpu.parallel.round import LAYERS

    monkeypatch.setattr(kda_scan, "fused_scan_applies", lambda *shapes: True)
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **policy: fn)
    with pltpu.force_tpu_interpret_mode():
        exp = build_experiment(tiny_kimi_linear())
        text = exp.make_step(1).lower(exp.state, exp.batch).as_text(
            debug_info=True)
    names = set(re.findall(
        r'"([^"]*/kda_scan_(?:forward|backward)/[^"]*)"', text))
    kernels = {re.search(r"kda_scan_(?:forward|backward)", n).group(0)
               for n in names}
    assert kernels == {"kda_scan_forward", "kda_scan_backward"}
    for name in names:
        assert re.search(r"kda\)*/kda_scan/kda_scan_(forward|backward)/", name)
        assert _stage_of(name, LAYERS) == "kda_scan", name
        assert (_pass_of(name, (), ()) == BACKWARD) == (
            "kda_scan_backward" in name), name


def test_what_the_registry_refuses():
    with pytest.raises(ValueError, match="do not name each"):
        build_model(dataclasses.replace(TINY, kda_layers=(1, 2, 3)))
    with pytest.raises(ValueError, match="do not name each"):
        build_model(dataclasses.replace(TINY, full_attn_layers=(4, 5)))
    with pytest.raises(ValueError, match="do not name each"):
        build_model(dataclasses.replace(TINY, num_hidden_layers=6))
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        build_model(dataclasses.replace(TINY, first_k_dense_replace=6))
    with pytest.raises(ValueError, match="no multi-token-prediction"):
        build_model(dataclasses.replace(TINY, num_nextn_predict_layers=1))
    with pytest.raises(ValueError, match="not among the 16"):
        build_model(dataclasses.replace(TINY, first_expert=14))
    with pytest.raises(ValueError, match="not whole chunks"):
        kda_scan.kda_scan(*_scan_inputs([1] * 72, 0.1)[:5],
                    jnp.ones((72,), jnp.int32), 64, jnp.float32)
