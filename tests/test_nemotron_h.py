"""The hybrid (``nemotron_h``) tower, fedtpu.models.nemotron_h, against its
plain reference (perfbench/reference_nemotron_h.py) and the reference against
the published code on this machine (``transformers``' Mamba2Mixer and
DeepseekV3TopkRouter): the loss and every gradient of a stack of all three
kinds on rows of two and three packed documents; documents packed into one
row against the documents alone, for the scan's state and the convolution,
at chunk sizes that do and do not divide them; the shares of an expert
layer adding up to the uncut layer; the loss over a vocabulary slice; the
blocks of the held-assignments buffer; what the registry refuses."""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from fedtpu.config import ModelConfig, get_preset
from fedtpu.models import layers
from fedtpu.models import nemotron_h as nh
from fedtpu.models.registry import build_model
from fedtpu.ops import ssm_passes
from fedtpu.training.task import build_task
from perfbench import flops_nemotron_h, reference_nemotron_h as ref
from tests.conftest import tiled_passes_interpreted

T = 64
TINY = ModelConfig(
    kind="nemotron_h", hidden_size=48, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=5,
    hybrid_override_pattern="ME*ME", mamba_num_heads=8, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, chunk_size=16, n_routed_experts=16,
    experts_held=4, first_expert=4, num_experts_per_tok=3,
    norm_topk_prob=True, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=40, vocab_size=128)
REFERENCE_KEYS = ("hybrid_override_pattern", "layer_norm_epsilon",
                  "mamba_num_heads", "mamba_head_dim", "n_groups",
                  "ssm_state_size", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "num_experts_per_tok",
                  "norm_topk_prob", "routed_scaling_factor", "first_expert")


def ref_cfg(cfg):
    return {k: getattr(cfg, k) for k in REFERENCE_KEYS}


def packed_row(rng, lengths, vocab=128, t=T):
    """One packed row ``(2, t)``: documents of ``lengths``, then padding."""
    row = np.zeros((2, t), np.int32)
    at = 0
    for seg, n in enumerate(lengths, start=1):
        row[0, at:at + n] = rng.integers(1, vocab, n)
        row[1, at:at + n] = seg
        at += n
    return row


def rows_of(lengths_a=(20, 30), lengths_b=(17, 23, 19), vocab=128):
    rng = np.random.default_rng(0)
    return jnp.asarray(np.stack([packed_row(rng, lengths_a, vocab),
                                 packed_row(rng, lengths_b, vocab)]))


def seeded(cfg, seed=0):
    """Seeded weights with every leaf away from its initial constant, so
    that no gradient is checked at a special point."""
    params = build_model(cfg)[0](jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), 64))
    jitter = lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
    for layer in params["mamba"]:
        for name in ("conv_b", "D", "gate_norm", "norm"):
            layer[name] = jitter(layer[name])
    return params


def program_loss(cfg, params, x):
    stats = build_model(cfg)[1](params, x, jnp.ones((x.shape[0],)))
    return stats["loss_sum"] / stats["count"], stats


def reference_loss(cfg, params, x):
    total = count = 0.0
    for row in x:
        loss, n = ref.sequence_loss(params, row, ref_cfg(cfg))
        total, count = total + loss, count + n
    return total / count


def relative_gaps(a, b):
    return jax.tree.map(lambda u, v: float(
        jnp.abs(u - v).max() / (jnp.abs(v).max() + 1e-12)), a, b)


# ------------------------------------------------- (a) loss and gradients
@pytest.fixture(scope="module")
def reference_side():
    """``(loss, grads)`` of the reference on the tiny stack's two rows."""
    reference = jax.jit(jax.value_and_grad(
        lambda p: reference_loss(TINY, p, rows_of())))
    with jax.default_matmul_precision("highest"):
        return reference(seeded(TINY))


@pytest.fixture(scope="module", params=["definitions", "tiled"])
def both_sides(request, reference_side):
    """``(program (loss, stats, grads), reference (loss, grads))`` of the
    tiny stack on two rows, of two and of three documents; the program's
    state-space mixers through the definitions of their two float32 passes
    (what a CPU runs by itself) and through the tiled bodies, interpreted."""
    params, x = seeded(TINY), rows_of()
    program = jax.jit(jax.value_and_grad(
        lambda p: program_loss(TINY, p, x), has_aux=True))
    with pytest.MonkeyPatch.context() as patch, (
            tiled_passes_interpreted(patch) if request.param == "tiled"
            else contextlib.nullcontext()):
        (loss, stats), grads = program(params)
    assert float(stats["ssm_fused_passes"]) == (
        2 * T if request.param == "tiled" else 0)
    return (loss, stats, grads), reference_side


def test_the_loss_is_the_references(both_sides):
    (loss, stats, _), (ref_loss, _) = both_sides
    assert abs(float(loss) - float(ref_loss)) <= 2e-6 * float(ref_loss)
    assert float(stats["count"]) == 20 + 30 + 17 + 23 + 19 - 5
    assert float(stats["padding"]) == 2 * T - 109


@pytest.mark.parametrize("part", ["embed", "mamba", "experts", "attention",
                                  "final_norm", "head"])
def test_every_gradient_is_the_references(both_sides, part):
    (_, _, grads), (_, ref_grads) = both_sides
    gaps = jax.tree.leaves(relative_gaps(grads[part], ref_grads[part]))
    assert gaps and max(gaps) <= 2e-4, gaps
    # no gradient reaches the selection bias, on either side
    if part == "experts":
        for side in (grads, ref_grads):
            assert all(float(jnp.abs(layer["router_bias"]).max()) == 0.0
                       for layer in side["experts"])
        assert all(float(jnp.abs(layer["router"]).max()) > 0
                   for layer in grads["experts"])


def test_the_counters_count_the_share_and_the_scan(both_sides):
    (_, stats, _), _ = both_sides
    task = build_task(TINY, build_model(TINY)[1], TINY.vocab_size)
    counters = task.counters(stats)
    real, e_layers, m_layers = 109, 2, 2
    assert float(counters["moe_assignments_total"]) == 3 * e_layers * real
    held = float(counters["moe_assignments_held"])
    assert 0 < held < 3 * e_layers * real
    assert float(counters["moe_tokens_dropped"]) == 0
    assert float(counters["moe_rows_computed"]) >= held
    assert float(counters["ssm_positions"]) == m_layers * 2 * T
    # five documents start from a zero state in each state-space layer
    assert float(counters["ssm_document_restarts"]) == m_layers * 5
    assert float(counters["lm_padding_tokens"]) == 2 * T - real
    assert counters["moe_expert_load"].shape == (16,)


# ----------------------------- (b) the reference against the published code
def _torch():
    try:
        import torch
        return torch
    except Exception as exc:        # pragma: no cover - torch is installed here
        pytest.skip(f"torch cannot be imported: {exc!r}")


def test_the_references_mixer_is_transformers_mamba2_mixer():
    torch = _torch()
    try:
        from transformers.models.mamba2.configuration_mamba2 import Mamba2Config
        from transformers.models.mamba2.modeling_mamba2 import Mamba2Mixer
    except Exception as exc:
        pytest.skip(f"transformers' Mamba2Mixer cannot be imported: {exc!r}")
    # n_groups 1: the published gated norm has no groups (the grouped form
    # is zamba2's Zamba2RMSNormGated)
    hidden, heads, p, n, k = 32, 8, 8, 16, 4
    conf = Mamba2Config(num_heads=heads, head_dim=p, hidden_size=hidden,
                        state_size=n, expand=2, conv_kernel=k, n_groups=1,
                        chunk_size=8, use_conv_bias=True, use_bias=False,
                        layer_norm_epsilon=1e-5, time_step_limit=(0.0, float("inf")))
    torch.manual_seed(0)
    mixer = Mamba2Mixer(conf, layer_idx=0).float().eval()
    with torch.no_grad():
        for prm in (mixer.conv1d.bias, mixer.D, mixer.dt_bias, mixer.norm.weight):
            prm.add_(0.3 * torch.randn_like(prm))
    x = torch.randn(1, 24, hidden)
    with torch.no_grad():
        want = mixer.torch_forward(x)[0].numpy()
    arr = lambda t: jnp.asarray(t.detach().numpy())
    layer = {"in_proj": arr(mixer.in_proj.weight).T,
             "conv_w": arr(mixer.conv1d.weight)[:, 0, :].T,
             "conv_b": arr(mixer.conv1d.bias), "dt_bias": arr(mixer.dt_bias),
             "A_log": arr(mixer.A_log), "D": arr(mixer.D),
             "gate_norm": arr(mixer.norm.weight),
             "out_proj": arr(mixer.out_proj.weight).T}
    cfg = {"mamba_num_heads": heads, "mamba_head_dim": p, "n_groups": 1,
           "ssm_state_size": n, "layer_norm_epsilon": 1e-5}
    with jax.default_matmul_precision("highest"):
        got = ref.mamba_mixer(layer, arr(x[0]), jnp.ones((24,), jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5)


def test_the_references_router_is_transformers_deepseek_v3_router():
    torch = _torch()
    try:
        from transformers.models.deepseek_v3.configuration_deepseek_v3 import \
            DeepseekV3Config
        from transformers.models.deepseek_v3.modeling_deepseek_v3 import \
            DeepseekV3TopkRouter
    except Exception as exc:
        pytest.skip(f"transformers' DeepseekV3TopkRouter cannot be imported: {exc!r}")
    conf = DeepseekV3Config(hidden_size=32, n_routed_experts=16,
                            num_experts_per_tok=3, n_group=1, topk_group=1,
                            norm_topk_prob=True, routed_scaling_factor=2.5)
    torch.manual_seed(1)
    router = DeepseekV3TopkRouter(conf)
    with torch.no_grad():
        router.weight.normal_(0, 0.3)
        router.e_score_correction_bias.normal_(0, 0.1)
    x = torch.randn(40, 32)
    with torch.no_grad():
        chosen, weights = router(x)
    want = np.zeros((40, 16), np.float32)
    np.put_along_axis(want, chosen.numpy(), weights.numpy(), axis=1)
    got = ref.gate_weights(jnp.asarray(x.numpy()),
                           jnp.asarray(router.weight.detach().numpy()).T,
                           jnp.asarray(router.e_score_correction_bias.numpy()),
                           3, True, 2.5)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-6)
    # and the program's: the same experts, the same weights
    gates, experts = layers.route(jnp.asarray(x.numpy()),
                              jnp.asarray(router.weight.detach().numpy()).T,
                              jnp.asarray(router.e_score_correction_bias.numpy()),
                              3, True, 2.5)
    mine = np.zeros((40, 16), np.float32)
    np.put_along_axis(mine, np.asarray(experts), np.asarray(gates), axis=1)
    np.testing.assert_allclose(mine, want, rtol=0, atol=1e-6)


# ------------------------------------- (c) packed documents are independent
def _scan_inputs(t, seed=0):
    heads, p, groups, n = 4, 8, 2, 8
    k = iter(jax.random.split(jax.random.key(seed), 5))
    return (jax.random.normal(next(k), (t, heads, p)),
            jax.nn.softplus(jax.random.normal(next(k), (t, heads))),
            -jnp.exp(jax.random.normal(next(k), (heads,))),
            jax.random.normal(next(k), (t, groups, n)),
            jax.random.normal(next(k), (t, groups, n)))


@pytest.mark.parametrize("chunk", [4, 8, 16, 32])
def test_two_packed_documents_scan_as_the_two_alone(chunk):
    """Documents of 24 and 40 positions in one row of 64: chunks of 4 and 8
    divide both, chunks of 16 and 32 straddle the edge."""
    x, dt, a, b, c = _scan_inputs(64)
    segs = jnp.asarray([1] * 24 + [2] * 40, jnp.int32)
    run, _ = ssm_passes.document_runs(segs)
    packed = nh.ssd_scan(x, dt, a, b, c, run, chunk, jnp.float32)
    ones = lambda n: jnp.ones((n,), jnp.int32)
    first = nh.ssd_scan(x[:24], dt[:24], a, b[:24], c[:24], ones(24),
                        min(chunk, 24) if 24 % chunk == 0 else 24, jnp.float32)
    second = nh.ssd_scan(x[24:], dt[24:], a, b[24:], c[24:], ones(40),
                         chunk if 40 % chunk == 0 else 40, jnp.float32)
    np.testing.assert_allclose(np.asarray(packed),
                               np.concatenate([first, second]), rtol=0,
                               atol=2e-5)
    # and the token-by-token recurrence agrees
    per_head = lambda arr: jnp.repeat(arr, 2, axis=1)
    starts = jnp.concatenate([jnp.ones((1,), bool), segs[1:] != segs[:-1]])
    with jax.default_matmul_precision("highest"):
        want = ref.recurrence(x, dt, a, per_head(b), per_head(c), starts)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(want), rtol=0,
                               atol=2e-5)


@pytest.mark.parametrize("body", ["definition", "tiled"])
def test_the_convolution_does_not_read_across_a_documents_edge(
        body, monkeypatch):
    """Through the definition, and through the tiled body (four tiles of 16
    rows: the first document ends mid-tile, the second a tile's row 13),
    which returns the convolution under its SiLU."""
    key = jax.random.key(3)
    x = jax.random.normal(key, (64, 6))
    w = jax.random.normal(jax.random.key(4), (4, 6))
    bias = jax.random.normal(jax.random.key(5), (6,))
    segs = jnp.asarray([1] * 24 + [2] * 37 + [0] * 3, jnp.int32)
    run, starts = ssm_passes.document_runs(segs)
    if body == "tiled":
        after = jax.nn.silu
        monkeypatch.setattr(ssm_passes, "CONV_TILE", (16, 6))
        tiled = jax.jit(lambda x, run: ssm_passes.conv_silu(
            x, w, bias, run, 0, 6, 6)[0])
        with pltpu.force_tpu_interpret_mode():
            packed = tiled(x, run)
    else:
        after = lambda pre: pre
        packed = ssm_passes.causal_conv(x, w, bias, run)
    ones = lambda n: jnp.ones((n,), jnp.int32)
    alone = [after(ssm_passes.causal_conv(x[lo:hi], w, bias, ones(hi - lo)))
             for lo, hi in ((0, 24), (24, 61), (61, 64))]
    np.testing.assert_allclose(np.asarray(packed), np.concatenate(alone),
                               rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(packed),
                               np.asarray(after(ref.conv(x, w, bias, starts))),
                               rtol=0, atol=1e-6)
    # the taps' order is the published one: w[K-1] weighs the token itself
    np.testing.assert_allclose(np.asarray(packed[0]),
                               np.asarray(after(x[0] * w[3] + bias)), atol=1e-6)


# ------------------- (c') the tiled passes against their definitions
def _segments(t, edges, padding=0):
    """Segment ids of ``t`` positions: a new document at every one of
    ``edges``, the last ``padding`` positions padding."""
    segs = 1 + np.searchsorted(np.asarray(edges), np.arange(t), side="right")
    segs[t - padding:] = 0
    return jnp.asarray(segs, jnp.int32)


def _close(got, want, names):
    for name, gap in zip(names, relative_gaps(tuple(got), tuple(want))):
        assert gap <= 2e-6, (name, gap)


def _with_gradients(body, weigh, n_args):
    """``args -> (body(*args), its gradients under the weights ``weigh``)``,
    jitted."""
    return jax.jit(lambda *args: (body(*args), jax.grad(
        lambda *a: (body(*a).astype(jnp.float32) * weigh).sum(),
        argnums=tuple(range(n_args)))(*args)))


# a tile is 16 rows here: where the runs' edges lie in it
RUN_EDGES = {
    "a_tiles_first_row": dict(edges=(16, 32, 48)),
    "a_tiles_row_1": dict(edges=(17, 49)),
    "a_tiles_row_2": dict(edges=(2, 18, 34)),
    "a_tiles_row_3": dict(edges=(3, 19, 51)),
    "a_tiles_last_rows": dict(edges=(13, 30, 47, 63)),
    "mid_tile": dict(edges=(8, 24, 40)),
    "a_run_longer_than_a_tile": dict(edges=(5, 50)),
    "one_run": dict(edges=()),
    "every_row_a_run": dict(edges=tuple(range(1, 64))),
    "a_padding_run_at_the_end": dict(edges=(30,), padding=7),
}


@pytest.mark.parametrize("first", [0, 64])
@pytest.mark.parametrize("where", RUN_EDGES)
def test_the_tiled_convolution_is_its_definition(where, first, monkeypatch):
    """``ssm_passes.conv_silu`` (interpreted; four tiles of 16 rows, two of
    64 columns) against ``silu(causal_conv)``: the result, its first tile of
    columns once more with the positions last, and the gradient of ``xBC``
    (read in place from column ``first`` of a wider array, whose other
    columns get a zero gradient), of the weight and of the bias, with a
    cotangent on both results."""
    width, width_t, total = 128, 64, 200
    keys = jax.random.split(jax.random.key(len(where) + first), 4)
    src = jax.random.normal(keys[0], (T, total))
    w = jax.random.normal(keys[1], (4, width))
    bias = jax.random.normal(keys[2], (width,))
    weigh = jax.random.normal(keys[3], (T, width + width_t))
    run, _ = ssm_passes.document_runs(_segments(T, **RUN_EDGES[where]))

    def definition(src, w, bias):
        out = jax.nn.silu(ssm_passes.causal_conv(src[:, first:first + width], w,
                                         bias, run))
        return jnp.concatenate([out, out[:, :width_t]], axis=1)

    monkeypatch.setattr(ssm_passes, "CONV_TILE", (16, 64))

    def tiled(src, w, bias):
        out, first_t = ssm_passes.conv_silu(src, w, bias, run, first, width,
                                            width_t)
        assert first_t.shape == (width_t, T)
        return jnp.concatenate([out, first_t.T], axis=1)

    with pltpu.force_tpu_interpret_mode():
        out, grads = _with_gradients(tiled, weigh, 3)(src, w, bias)
    want, want_grads = _with_gradients(definition, weigh, 3)(src, w, bias)
    _close((out, *grads), (want, *want_grads),
           ("out", "xBC", "conv_w", "conv_b"))
    outside = np.ones(total, bool)
    outside[first:first + width] = False
    assert not np.asarray(grads[0])[:, outside].any()


@pytest.mark.parametrize("tile", [(16, 32), (32, 64), (64, 16), (8, 64)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_tiled_gate_and_norm_is_its_definition(tile, dtype, monkeypatch):
    """``ssm_passes.skip_gate_norm`` (interpreted; tiles of one group of 16
    columns, of two, of all four) against the ``D`` skip and
    ``gated_group_norm``: the result, rounded once to ``dtype``, and the
    gradient of ``y`` (handed over chunk-transposed, chunks of 8 rows), of
    ``x`` and ``z`` (the first columns of wider arrays, read in place), of
    the gain and of ``D``."""
    width, heads, groups, eps = 64, 8, 4, 1e-5
    keys = jax.random.split(jax.random.key(sum(tile)), 6)
    y = jax.random.normal(keys[0], (T, width))
    xs = jax.random.normal(keys[1], (T, 128))
    zs = jax.random.normal(keys[2], (T, 200))
    skip = 1 + 0.3 * jax.random.normal(keys[3], (heads,))
    gain = 1 + 0.3 * jax.random.normal(keys[4], (width,))
    weigh = jax.random.normal(keys[5], (T, width))

    def definition(y, xs, zs, skip, gain):
        x = xs[:, :width].reshape(T, heads, -1)
        v = (y.reshape(T, heads, -1) + skip[:, None] * x).reshape(T, width)
        return ssm_passes.gated_group_norm(v, zs[:, :width], gain, groups,
                                   eps).astype(dtype)

    monkeypatch.setattr(ssm_passes, "GATE_TILE", tile)

    def tiled(y, xs, zs, skip, gain):
        return ssm_passes.skip_gate_norm(
            ssm_passes.chunk_transposed(y, 8), xs, zs, skip, gain, groups,
            eps, jnp.dtype(dtype))

    args = (y, xs, zs, skip, gain)
    with pltpu.force_tpu_interpret_mode():
        out, grads = _with_gradients(tiled, weigh, 5)(*args)
    want, want_grads = _with_gradients(definition, weigh, 5)(*args)
    assert out.dtype == want.dtype == jnp.dtype(dtype)
    # rounded once from float32 on both sides: the same value, or (a float32
    # tie apart) its neighbour
    out, want = out.astype(jnp.float32), want.astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=0,
        rtol=2e-6 if dtype == "float32" else 2 ** -7)
    _close(grads, want_grads, ("y", "x", "z", "D", "gate_norm"))
    assert not np.asarray(grads[1])[:, width:].any()
    assert not np.asarray(grads[2])[:, width:].any()


def test_two_packed_documents_give_the_losses_of_the_two_alone():
    params = seeded(TINY)
    rng = np.random.default_rng(1)
    together = packed_row(rng, (24, 40))
    def alone(lo, hi):
        row = np.zeros((2, T), np.int32)
        row[0, :hi - lo] = together[0, lo:hi]
        row[1, :hi - lo] = 1
        return row
    stats = build_model(TINY)[1]
    one = lambda row: stats(params, jnp.asarray(row)[None], jnp.ones((1,)))
    packed, first, second = one(together), one(alone(0, 24)), one(alone(24, 64))
    assert float(packed["count"]) == float(first["count"] + second["count"])
    np.testing.assert_allclose(float(packed["loss_sum"]),
                               float(first["loss_sum"] + second["loss_sum"]),
                               rtol=2e-6)


# ------------------------------------------------ (d) the shares add up
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """16 routed experts in 4 shares of 4: the four partial results, with
    the shared expert (which every chip computes alike) counted once, are
    the uncut reference layer's."""
    whole = dataclasses.replace(TINY, experts_held=0, first_expert=0)
    key = jax.random.key(7)
    layer = layers._experts_init(
        whole, lambda *s: 0.3 * jax.random.normal(
            jax.random.fold_in(key, sum(s) + len(s)), s),
        lambda *s: jnp.ones(s), key, jnp.float32)
    layer["norm"] = layer["norm"] + 0.1 * jax.random.normal(key, (48,))
    h = jax.random.normal(jax.random.key(8), (T, 48))
    segs = jnp.asarray([1] * 30 + [2] * 34, jnp.int32)
    x = ref._rms(h, layer["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        uncut = ref.experts_mixer(layer, x, ref_cfg(whole))
        shared = ref.expert(x, layer["shared_up"], layer["shared_down"])
    total, held_sum = 0.0, 0.0
    for first in (0, 4, 8, 12):
        share = dataclasses.replace(TINY, experts_held=4, first_expert=first)
        part = {**layer, "up": layer["up"][first:first + 4],
                "down": layer["down"][first:first + 4]}
        out, stats = layers.experts_mixer(share, jnp.float32, h, part, segs)
        total, held_sum = total + out, held_sum + stats["assignments_held"]
        # the reference given the same share gives the same part
        with jax.default_matmul_precision("highest"):
            want = ref.experts_mixer(part, x, ref_cfg(share))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=0, atol=5e-5)
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(uncut), rtol=0, atol=1e-4)
    assert float(held_sum) == 3 * T          # every assignment, exactly once


# The XLA body (``lax.ragged_dot``) at any block size; the tiled body (the
# grouped Pallas kernels, interpreted, always under jit) at whole row tiles:
# one block longer than its groups (the kernels visit no tile past the last
# group and leave those rows undefined, as the TPU's ``ragged_dot`` does),
# and 256 tokens whose held assignments take two blocks.
@pytest.mark.parametrize("body,tokens,rows", [
    ("xla", 64, 8), ("xla", 64, 16), ("xla", 64, 64), ("xla", 64, 256),
    ("grouped", 64, 256), ("grouped", 256, 256)])
def test_the_held_buffer_is_exact_in_any_number_of_blocks(
        monkeypatch, request, body, tokens, rows):
    """The same layer with the held assignments in blocks of 8 rows (many
    trips), 16, 64 and 256 (one), through either body of the grouped
    matmuls: the reference's output and the reference's gradients (of the
    input, of both expert weights and, through the router's, of the
    gates)."""
    if body == "grouped":
        request.getfixturevalue("grouped_on_the_cpu")
    # the tiled kernels take whole row tiles, as ``held_block_rows`` gives
    rows = rows if body == "grouped" else min(rows, 3 * tokens)
    monkeypatch.setattr(layers, "held_block_rows", lambda a, share: rows)
    cfg = dataclasses.replace(TINY, experts_held=8, first_expert=2)
    key = jax.random.key(11)
    layer = layers._experts_init(
        cfg, lambda *s: 0.3 * jax.random.normal(
            jax.random.fold_in(key, sum(s) + len(s)), s),
        lambda *s: jnp.ones(s), key, jnp.float32)
    real = tokens * 25 // 32
    h = jax.random.normal(jax.random.key(12), (tokens, 48))
    segs = jnp.asarray([1] * real + [0] * (tokens - real), jnp.int32)

    def mine(layer, h):
        out, stats = layers.experts_mixer(cfg, jnp.float32, h, layer, segs)
        return (out[:real] ** 2).sum(), stats

    def theirs(layer, h):
        out = ref.experts_mixer(layer, ref._rms(h, layer["norm"], 1e-5),
                                ref_cfg(cfg))
        return (out[:real] ** 2).sum()

    program = jax.jit(jax.value_and_grad(mine, argnums=(0, 1), has_aux=True))
    reference = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1)))
    (loss, stats), grads = program(layer, h)
    with jax.default_matmul_precision("highest"):
        want, want_grads = reference(layer, h)
    assert abs(float(loss) - float(want)) <= 1e-5 * float(want)
    gaps = jax.tree.leaves(relative_gaps(
        (grads[0], grads[1][:real]), (want_grads[0], want_grads[1][:real])))
    assert max(gaps) <= 2e-4, gaps
    held = int(stats["assignments_held"])
    assert int(stats["rows_computed"]) == -(-held // rows) * rows
    if body == "grouped":   # a block longer than its groups; two blocks
        assert (-(-held // rows), held % rows > 0) == (1 + tokens // 256, True)
    assert int(stats["rows_held_computed"]) == held     # nothing dropped
    # padding is routed nowhere: its tokens are in no count
    assert int(stats["expert_load"].sum()) == 3 * real


@pytest.mark.parametrize("body", ["xla", "grouped"])
def test_the_counter_says_which_body_the_held_experts_ran(body, request):
    """``grouped_experts`` reads the rule between the bodies as the held
    experts' two products read it: every position where the tiled kernels
    ran, none where ``lax.ragged_dot`` did (a CPU, by itself)."""
    if body == "grouped":
        request.getfixturevalue("grouped_on_the_cpu")
    sequence_stats = jax.jit(lambda p, r: nh.sequence_stats(
        p, r, TINY, jnp.float32))
    stats = sequence_stats(seeded(TINY), rows_of()[0])
    assert int(stats["grouped_experts"]) == (T if body == "grouped" else 0)
    assert int(stats["rows_held_computed"]) == int(stats["assignments_held"]) > 0


@pytest.mark.parametrize("body", ["definitions", "tiled"])
def test_the_counter_says_which_body_the_two_passes_ran(body, request):
    """``ssm_fused_passes`` reads the rule between the bodies as the mixers
    read it: every position where the tiled passes ran, none where the
    definitions did (a CPU, by itself); the task hands it on as
    ``ssm_fused_pass_positions``."""
    if body == "tiled":
        request.getfixturevalue("tiled_passes_on_the_cpu")
    sequence_stats = jax.jit(lambda p, r: nh.sequence_stats(
        p, r, TINY, jnp.float32))
    stats = sequence_stats(seeded(TINY), rows_of()[0])
    assert int(stats["ssm_fused_passes"]) == (T if body == "tiled" else 0)
    counters = build_task(TINY, build_model(TINY)[1],
                          TINY.vocab_size).counters(stats)
    assert int(counters["ssm_fused_pass_positions"]) == (
        T if body == "tiled" else 0)
    assert int(counters["ssm_positions"]) == 2 * T


def test_the_rule_between_the_passes_bodies_reads_shapes_and_the_backend(
        monkeypatch):
    """``fused_passes_apply``: on a TPU whole row tiles, and the inner
    width, ``xBC``'s and a group of the norm whole lane tiles; nowhere on
    a CPU."""
    cell = get_preset("nemotron-h-30b-a3b-l9").model
    assert not ssm_passes.fused_passes_apply(cell, 8192)         # this is a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm_passes.fused_passes_apply(cell, 8192)
    assert ssm_passes.fused_passes_apply(cell, 1024)
    assert not ssm_passes.fused_passes_apply(cell, 8192 + 256)   # no whole row tile
    assert not ssm_passes.fused_passes_apply(TINY, 1024)         # widths of 64, 128
    narrow = dataclasses.replace(cell, n_groups=64)      # a group of 64
    assert not ssm_passes.fused_passes_apply(narrow, 8192)
    odd = dataclasses.replace(cell, ssm_state_size=100)  # xBC 5,696 wide
    assert not ssm_passes.fused_passes_apply(odd, 8192)
    long = dataclasses.replace(cell, conv_kernel=12)     # past a halo block
    assert not ssm_passes.fused_passes_apply(long, 8192)


# ------------------------------------------------ (e) the vocabulary slice
def test_the_loss_over_a_vocabulary_slice():
    """A slice of the vocabulary is a smaller vocabulary: with ids drawn from
    the slice, the sliced embedding and head give the loss of the whole
    model's logits cut to the slice's columns."""
    whole = dataclasses.replace(TINY, vocab_size=512)
    params = seeded(whole)
    cut = {**params, "embed": params["embed"][:128],
           "head": params["head"][:, :128]}
    x = rows_of(vocab=128)
    loss, _ = program_loss(TINY, cut, x)

    def sliced_reference(p, row):
        # the reference's own layers; the head's log-softmax over the slice
        tokens, segs = row[0], row[1]
        c = ref_cfg(whole)
        h = p["embed"][tokens]
        for kind, layer in ref.layers_of(p, c):
            xn = ref._rms(h, layer["norm"], 1e-5)
            h = h + (ref.mamba_mixer(layer, xn, segs, c) if kind == "mamba"
                     else ref.attention_mixer(layer, xn, segs, c)
                     if kind == "attention" else ref.experts_mixer(layer, xn, c))
        logits = (ref._rms(h, p["final_norm"], 1e-5) @ p["head"])[:, :128]
        logp = jax.nn.log_softmax(logits, axis=-1)
        labels = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
        nxt = jnp.concatenate([segs[1:], jnp.zeros((1,), segs.dtype)])
        valid = ((segs > 0) & (nxt == segs)).astype(jnp.float32)
        ll = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        return -(ll * valid).sum(), valid.sum()

    with jax.default_matmul_precision("highest"):
        parts = [sliced_reference(params, row) for row in x]
    want = sum(p[0] for p in parts) / sum(p[1] for p in parts)
    assert abs(float(loss) - float(want)) <= 2e-6 * float(want)


# --------------------------------------------------- sizes and refusals
def test_the_published_widths_count_the_configurations_parameters():
    cfg = get_preset("nemotron-h-30b-a3b-l9").model
    shapes = jax.eval_shape(build_model(cfg)[0], jax.random.key(0))
    count = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert count == 666_963_456
    count_of = lambda tree: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
    assert count_of(shapes["mamba"][0]) == 38_744_896
    assert count_of(shapes["attention"][0]) == 23_399_040
    assert count_of(shapes["experts"][0]) == 100_125_440
    assert [len(shapes[k]) for k in ("mamba", "experts", "attention")] == [4, 4, 1]
    # the benchmark's own count, from the configuration's keys
    keys = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    assert flops_nemotron_h.params(keys)["total"] == count
    tiny = {f.name: getattr(TINY, f.name) for f in dataclasses.fields(TINY)}
    tiny_shapes = jax.eval_shape(build_model(TINY)[0], jax.random.key(0))
    assert flops_nemotron_h.params(tiny)["total"] == sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(tiny_shapes))


@pytest.mark.parametrize("pattern", ["*MMEM", "EMME*", "MMM", "ME*ME*"])
def test_the_stack_is_read_from_the_pattern(pattern):
    """The kinds in any order, a kind twice in a row, one kind alone."""
    cfg = dataclasses.replace(TINY, hybrid_override_pattern=pattern,
                              num_hidden_layers=len(pattern))
    assert nh.layer_kinds(cfg) == tuple(nh.KINDS[c] for c in pattern)
    loss, _ = program_loss(cfg, seeded(cfg), rows_of())
    want = reference_loss(cfg, seeded(cfg), rows_of())
    assert abs(float(loss) - float(want)) <= 5e-6 * float(want)


def test_each_kinds_layers_lie_under_its_own_subtree():
    cfg = dataclasses.replace(TINY, hybrid_override_pattern="*MMEM",
                              num_hidden_layers=5)
    shapes = jax.eval_shape(build_model(cfg)[0], jax.random.key(0))
    assert [len(shapes[k]) for k in ("mamba", "experts", "attention")] == [3, 1, 1]
    none = dataclasses.replace(TINY, hybrid_override_pattern="MMM",
                               num_hidden_layers=3)
    shapes = jax.eval_shape(build_model(none)[0], jax.random.key(0))
    assert shapes["experts"] == () and shapes["attention"] == ()


@pytest.mark.parametrize("change, message", [
    (dict(hybrid_override_pattern="ME-ME"), "letters"),
    (dict(num_hidden_layers=4), "num_hidden_layers"),
    (dict(first_expert=14), "not among"),
    (dict(num_key_value_heads=3), "key-value heads"),
    (dict(n_groups=3), "groups"),
])
def test_the_registry_refuses_what_the_stack_cannot_run(change, message):
    with pytest.raises(ValueError, match=message):
        build_model(dataclasses.replace(TINY, **change))


def test_the_held_block_is_whole_tiles_at_eight_thirds_of_the_mean():
    # the benchmark's step: 8,192 tokens x 6 choices, 8 of 128 experts held
    assert layers.held_block_rows(8192 * 6, 8 / 128) == 8192
    assert layers.held_block_rows(4096 * 6, 8 / 128) == 4096
    assert layers.held_block_rows(64 * 3, 4 / 16) == 256        # one tile at least
    assert layers.held_block_rows(8192 * 6, 1.0) == 8192 * 6    # never past all
