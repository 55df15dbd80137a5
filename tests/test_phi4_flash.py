"""The decoder-hybrid-decoder stack, fedtpu.models.phi4_flash, against its
plain reference (perfbench/reference_phi4_flash.py): the chunked selective
scan against the token-by-token one, values and every gradient, over chunk
sizes that do and do not divide a document, with restarts inside a chunk and
at a chunk's edge; the windowed table of kept block pairs against a
brute-force mask, and without a window against the parent's formula; the
windowed core's two bodies; two federated rounds through ``run_experiment``
(every client's loss, every global parameter, the counters); the loss and
every leaf's gradient, the tied embedding's among them; each mechanism
switched off in turn; what a lower precision does to the tolerances; the
vocabulary's slices side by side; the parameter count of the published
configuration; the scopes; what the registry refuses."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtpu.config import ModelConfig, TelemetryConfig, get_preset
from fedtpu.models import layers
from fedtpu.models import phi4_flash as phi
from fedtpu.models.registry import build_model
from fedtpu.ops import lm_head, packed_attention, selective_scan, ssm_passes
from fedtpu.orchestration.loop import build_experiment, run_experiment
from perfbench import reference_phi4_flash as ref

T = 128
# the published pattern at a depth of 8: layers 0-3 the self-decoder, 4 the
# memory's Mamba-1, 5 full attention, 6 a Gated Memory Unit, 7 cross-attention
TINY = ModelConfig(
    kind="phi4_flash", hidden_size=32, num_attention_heads=4,
    num_key_value_heads=2, num_hidden_layers=8, intermediate_size=64,
    sliding_window=16, mamba_d_state=4, tie_word_embeddings=True,
    vocab_size=128)
# float32 on both sides: what differs is the order of the sums (the scan's
# chunks, the head's chunks of rows, the softmax's), so a loss near 4.9 agrees
# to 1e-5 and a leaf's gradient to 5e-5 of its largest entry; the largest gaps
# seen are 6e-7 and 1.2e-5 (and 1e-4 of a lambda vector's gradient of 5e-6).
# A bfloat16 anywhere puts some gradient ten times outside
# (``test_a_lower_precision_fails_the_tolerances``).
LOSS_TOL, GRAD_TOL = 1e-5, 5e-5


def ref_cfg(cfg):
    """The reference's dictionary of a ModelConfig, under the published
    config's own key names."""
    return {k: getattr(cfg, k) for k in (
        "num_attention_heads", "num_key_value_heads", "num_hidden_layers",
        "mb_per_layer", "layers_held", "sliding_window", "layer_norm_eps")}


def packed_row(rng, lengths, vocab=128, t=T):
    row = np.zeros((2, t), np.int32)
    at = 0
    for seg, n in enumerate(lengths, start=1):
        row[0, at:at + n] = rng.integers(1, vocab, n)
        row[1, at:at + n] = seg
        at += n
    return row


def seeded(cfg, seed=0):
    """Seeded weights with every gain, bias, lambda and the Mamba mixers' own
    leaves away from their starts, so that no gradient is checked at a
    special point (a zero bias, ``D = 1``)."""
    params = build_model(cfg)[0](jax.random.key(seed))
    count = iter(range(10_000))

    def jitter(path, leaf):
        if leaf.ndim > 1 and "A_log" not in jax.tree_util.keystr(path):
            return leaf
        return leaf + 0.1 * jax.random.normal(
            jax.random.fold_in(jax.random.key(seed + 1), next(count)),
            leaf.shape)

    return jax.tree_util.tree_map_with_path(jitter, params)


def program_loss(cfg, params, x):
    """``(mean loss, statistics)`` of the rows ``x (N, 2, T)`` through the
    program's own ``stats_fn``."""
    stats_fn = jax.jit(build_model(cfg)[1])
    stats = stats_fn(params, jnp.asarray(x), jnp.ones((len(x),), jnp.float32))
    return stats["loss_sum"] / stats["count"], stats


def reference_loss(cfg, params, x):
    with jax.default_matmul_precision("highest"):
        parts = [ref.sequence_loss(params, jnp.asarray(row), ref_cfg(cfg))
                 for row in x]
    return sum(p[0] for p in parts) / sum(p[1] for p in parts)


def rows_of(vocab=128, seed=0):
    rng = np.random.default_rng(seed)
    # documents longer than the window of 16; one row with padding
    return np.stack([packed_row(rng, (50, 40, 30), vocab),
                     packed_row(rng, (100, 28), vocab)])


def _gap(a, b):
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def tiny_phi4_flash(rounds=2, **run):
    cfg = get_preset("phi4-mini-flash-l8")
    assert cfg.fed.one_step_kind        # the preset's: one trace of the model
    return cfg.replace(
        model=dataclasses.replace(TINY, compute_dtype="float32"),
        data=dataclasses.replace(cfg.data, synthetic_rows=10,
                                 synthetic_features=T),
        shard=dataclasses.replace(cfg.shard, num_clients=4),
        optim=dataclasses.replace(cfg.optim, learning_rate=0.1),
        fed=dataclasses.replace(cfg.fed, rounds=rounds, init_seed=3),
        run=dataclasses.replace(cfg.run, mesh_devices=1, **run))


# ------------------------------ (a) the chunked scan is the plain recurrence
def _scan_inputs(segs, d=24, n=8, seed=0):
    """What a mixer hands its scan: ``x`` after a SiLU, steps after a
    softplus around the published start (0.001 to 0.1) and some of them
    large, ``A = -(1 .. N)``."""
    t = len(segs)
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.nn.silu(jax.random.normal(ks[0], (t, d)))
    dl = jax.nn.softplus(2.0 * jax.random.normal(ks[1], (t, d)) - 3.0)
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1), (d, n)) * jnp.exp(
        0.1 * jax.random.normal(ks[2], (d, n)))
    b, c = (jax.random.normal(k, (t, n)) for k in ks[3:])
    run, _ = ssm_passes.document_runs(jnp.asarray(segs, jnp.int32))
    return x, dl, a, b, c, run


SEVERAL = [1] * 37 + [2] * 27 + [3] * 32 + [4] * 21 + [0] * 11
ONE = [1] * T


@pytest.mark.parametrize("segs,chunk", [
    (SEVERAL, 32), (SEVERAL, 64), (SEVERAL, 16), (SEVERAL, 128), (ONE, 32),
    (SEVERAL[:96], 64), (SEVERAL[:100], 30)],
    ids=["chunk32-a-start-at-an-edge", "chunk64", "chunk16-sub-blocks-of-16",
         "one-chunk", "one-document", "a-row-of-no-whole-chunks",
         "a-row-of-100-as-one-chunk"])
def test_the_chunked_scan_is_the_token_by_token_one(segs, chunk):
    """Values and the gradient of every input, float32: the order of the sums
    differs and nothing else. ``SEVERAL`` starts documents at 37 (inside a
    chunk of any size here), at 64 (the edge of a chunk of 16, 32 and 64) and
    at 96 (an edge of 16 and 32): a state crosses edges and restarts at and
    beside them. The largest gaps seen: 1e-6 on values near 1 and 6e-7 of a
    gradient's largest entry."""
    args = _scan_inputs(segs)
    w = jax.random.normal(jax.random.key(9), args[0].shape)
    ours = lambda *a: selective_scan.selective_scan(*a, args[5], chunk)
    plain = lambda *a: selective_scan.plain_scan(*a, args[5])
    with jax.default_matmul_precision("highest"):
        scan, scan_grads = jax.jit(ours), jax.jit(jax.grad(
            lambda *a: (ours(*a) * w).sum(), argnums=range(5)))
        y, want = scan(*args[:5]), plain(*args[:5])
        grads = scan_grads(*args[:5])
        wants = jax.grad(lambda *a: (plain(*a) * w).sum(),
                         argnums=range(5))(*args[:5])
    assert float(jnp.abs(y - want).max()) <= 5e-6
    for got, exact in zip(grads, wants):
        assert float(jnp.abs(got - exact).max()) <= 5e-6 * float(
            jnp.abs(exact).max())


def test_a_document_packed_behind_another_scans_as_it_does_alone():
    """The state is zero at a document's first token: the second document's
    outputs are those of the document alone at the row's start, and a
    restart that is left out changes them."""
    both = _scan_inputs([1] * 40 + [2] * 88)
    alone = tuple(a[40:] if a.shape[0] == T else a for a in both[:5]) + (
        both[5][40:] - 1,)
    scan = jax.jit(lambda *a: selective_scan.selective_scan(*a, 32))
    packed, single = scan(*both)[40:], scan(*alone)
    assert float(jnp.abs(packed - single).max()) <= 2e-6
    merged = scan(*both[:5], jnp.ones_like(both[5]))[40:]
    assert float(jnp.abs(merged - single).max()) > 1e-2


# --------------------------------------- (b) the window in the tiled core
def _brute_force_kept(segs, block, window):
    """A pair of blocks is needed iff it holds one allowed (query, key)."""
    segs = np.asarray(segs)
    t = len(segs)
    at = np.arange(t)
    allowed = (at[:, None] >= at[None, :]) & (segs[:, None] == segs[None, :])
    if window is not None:
        allowed &= at[:, None] - at[None, :] < window
    n = t // block
    return allowed.reshape(n, block, n, block).any(axis=(1, 3))


@pytest.mark.parametrize("window", [None, 16, 24, 40, 64])
def test_the_windowed_table_keeps_every_block_pair_the_mask_needs(window):
    """The table is conservative (it may keep a pair whose ranges of ids
    overlap and that holds no allowed pair) and never drops a needed one;
    under a window it keeps no pair wholly outside it. Without a window it is
    the parent's table, entry for entry."""
    rng = np.random.default_rng(0)
    block = 16
    for lengths in ((50, 40, 30), (128,), (16, 16, 33, 60), (5, 100)):
        segs = packed_row(rng, lengths)[1]
        kept = np.asarray(packed_attention.pairs_kept(
            jnp.asarray(segs), block, window))
        needed = _brute_force_kept(segs, block, window)
        assert not (needed & ~kept).any()
        blocks = np.arange(T // block)
        if window is not None:
            # first query - last key of a pair of blocks
            least = ((blocks[:, None] - blocks[None, :]) * block
                     - (block - 1))
            assert not (kept & (least >= window)).any()
            assert kept.sum() < np.asarray(packed_attention.pairs_kept(
                jnp.asarray(segs), block)).sum() or len(lengths) > 2
        else:
            ranges = np.asarray(packed_attention.block_ranges(
                jnp.asarray(segs), block))
            lo, hi = ranges[:, 0], ranges[:, 1]
            parents = ((blocks[:, None] >= blocks[None, :])
                       & (lo[:, None] <= hi[None, :])
                       & (lo[None, :] <= hi[:, None]))
            assert (kept == parents).all()


def test_the_windowed_core_is_the_masked_softmax_in_both_bodies(monkeypatch):
    """The XLA body under a window against the mask written out, and the
    tiled body (interpreted, blocks of 128 in 256 positions, a window of 100
    that cuts inside a block) against the XLA body: context and the three
    gradients at the bf16 kernels' own tolerance."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(1)
    t, heads, d, window = 256, 2, 128, 100
    segs = jnp.asarray(packed_row(rng, (150, 90), t=t)[1])
    q, k, v = (jnp.asarray(rng.normal(size=(t, heads, d)), jnp.float32)
               for _ in range(3))
    at = np.arange(t)
    allowed = ((at[:, None] >= at[None, :])
               & (np.asarray(segs)[:, None] == np.asarray(segs)[None, :])
               & (at[:, None] - at[None, :] < window))
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
        want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
            jnp.where(allowed[None], scores, -jnp.inf), axis=-1), v)
        xla = packed_attention.attention_core(q, k, v, segs, jnp.float32,
                                              window=window)
    assert float(jnp.abs(xla - want).max()) <= 1e-5
    full = packed_attention.attention_core(q, k, v, segs, jnp.float32)
    assert float(jnp.abs(xla - full).max()) > 1e-2      # the window cuts

    w = jnp.asarray(rng.normal(size=(t, heads, d)), jnp.float32)
    loss = lambda q, k, v: (packed_attention.attention_core(
        q, k, v, segs, jnp.bfloat16, window=window) * w).sum()
    by_xla = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    plain = by_xla(q, k, v)
    monkeypatch.setattr(packed_attention, "ATTENTION_BLOCK", 128)
    monkeypatch.setattr(packed_attention, "fused_attention_applies",
                        lambda *a: True)
    # a function of its own: the trace under the other rule is not reused
    tiled_loss = lambda q, k, v: loss(q, k, v)
    assert "pallas_call" in str(jax.make_jaxpr(tiled_loss)(q, k, v))
    assert "pallas_call" not in str(by_xla.trace(q, k, v).jaxpr)
    by_kernels = jax.jit(jax.value_and_grad(tiled_loss, argnums=(0, 1, 2)))
    with pltpu.force_tpu_interpret_mode():
        tiled = by_kernels(q, k, v)
    assert abs(float(tiled[0] - plain[0])) <= 2e-2 * abs(float(plain[0])) + 0.5
    for got, exact in zip(tiled[1], plain[1]):
        assert float(jnp.abs(got - exact).max()) <= 3e-2 * float(
            jnp.abs(exact).max())


# ------------------------------------ (c) two rounds through run_experiment
def test_two_rounds_through_run_experiment_match_the_references_fedavgm(
        tmp_path, monkeypatch):
    """float32 on both sides: the gaps are the order of the sums, so 2e-5 on
    losses near 4.9 and on parameters that moved by 1e-2, as the other
    language models' rounds. Rows of 128 tokens of short documents: a scan's
    state crosses a chunk's edge and others start inside one, and documents
    pass the window of 16."""
    monkeypatch.setattr("fedtpu.data.tokens.DOC_MEDIAN", 30.0)
    monkeypatch.setattr(selective_scan, "CHUNK", 32)
    sink = str(tmp_path / "ev.jsonl")
    cfg = tiny_phi4_flash(telemetry=TelemetryConfig(events_path=sink))
    result = run_experiment(cfg, verbose=False)
    ds = build_experiment(cfg).dataset
    rows = [ds.x_train[ds.client_of_row == c] for c in range(4)]
    assert sorted(len(r) for r in rows) == [1, 2, 3, 4]         # size skew
    init = jax.tree.map(np.asarray, build_model(cfg.model)[0](
        jax.random.key(cfg.fed.init_seed)))
    with jax.default_matmul_precision("highest"):
        want, ref_params = ref.fedavgm_rounds(
            init, rows, 2, ref_cfg(cfg.model),
            learning_rate=cfg.optim.learning_rate,
            momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr)
    assert np.max(np.abs(np.stack(result.loss) - want)) <= 2e-5
    assert _gap(result.final_params, ref_params) <= 2e-5
    assert _gap(result.final_params, init) > 1e-3               # it moved
    events = [json.loads(line) for line in open(sink)]
    counted = [e for e in events
               if e["kind"] == "counters"][-1]["payload"]["counters"]
    segs = ds.x_train[:, 1]
    starts = int(((segs > 0) & (np.pad(segs, ((0, 0), (1, 0)))[:, :-1]
                                != segs)).sum())
    lengths = np.concatenate([np.bincount(r[r > 0])[1:] for r in segs])
    pairs = int((lengths * (lengths + 1) // 2).sum())
    windowed = int(sum(min(i + 1, 16) for n in lengths for i in range(n)))
    assert not [name for name in counted if name.startswith("moe_")]
    assert counted["stateless_client_steps"] == 2 * 10
    assert counted["s6_positions"] == 2 * 10 * T * 3    # three Mamba-1 layers
    assert counted["s6_chunked_scan_positions"] == 2 * 10 * T
    assert counted["s6_fused_scan_positions"] == 0      # a CPU: the XLA body
    assert counted["s6_fused_conv_positions"] == 0      # a CPU
    assert counted["s6_document_restarts"] == 2 * 3 * starts
    assert starts > 20                                  # several a row
    assert counted["lm_attention_pairs"] == 2 * pairs
    assert counted["lm_window_pairs"] == 2 * windowed < 2 * pairs
    assert counted["lm_fused_attention_positions"] == 0


# ----------------------------------- the loss and every gradient, one step
def test_the_loss_and_every_gradient_are_the_references():
    """Rows of two and three packed documents and padding, every small leaf
    away from its start, float32: the loss to ``LOSS_TOL`` and every leaf's
    gradient to ``GRAD_TOL`` of the leaf's largest entry, the tied
    embedding's (the head's gradient and the rows' in one leaf) among them."""
    params, x = seeded(TINY), rows_of()
    loss_of = lambda p: program_loss(TINY, p, x)[0]
    want_of = lambda p: reference_loss(TINY, p, x)
    loss, grads = jax.value_and_grad(loss_of)(params)
    with jax.default_matmul_precision("highest"):
        want, wants = jax.value_and_grad(want_of)(params)
    assert abs(float(loss - want)) <= LOSS_TOL
    flat = jax.tree_util.tree_flatten_with_path(wants)[0]
    assert len(flat) == len(jax.tree.leaves(grads)) > 80
    for (path, exact), got in zip(flat, jax.tree.leaves(grads)):
        assert float(jnp.abs(exact).max()) > 0, jax.tree_util.keystr(path)
        # a leaf whose gradient is a small difference of large sums (a
        # lambda vector's, 5e-6) is held to float32's rounding of the sums
        assert float(jnp.abs(got - exact).max()) <= GRAD_TOL * float(
            jnp.abs(exact).max()) + 2e-9, jax.tree_util.keystr(path)


# --------------------------- (d) each mechanism, switched off in turn
def _without(name, params, x, monkeypatch):
    """The program's loss with one mechanism off."""
    cfg = TINY
    if name == "window":            # a window no document reaches
        cfg = dataclasses.replace(TINY, sliding_window=T)
    elif name == "memory":
        real = phi.gmu_mixer
        monkeypatch.setattr(phi, "gmu_mixer", lambda c, d, u, layer, memory:
                            real(c, d, u, layer, jnp.zeros_like(memory)))
    elif name == "shared-keys-and-values":
        # the cross layer reads its own input's projections of nothing: zeros
        real = phi.attention_mixer

        def own(kind, index, c, d, u, layer, segs, shared):
            if kind == "cross":
                shared = jax.tree.map(jnp.zeros_like, shared)
            return real(kind, index, c, d, u, layer, segs, shared)
        monkeypatch.setattr(phi, "attention_mixer", own)
    elif name == "lambda":          # the second softmax is not subtracted
        monkeypatch.setattr(phi, "lambda_init", lambda index: 0.0)
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf * 0.0 - 30.0
            if "lambda_q" in jax.tree_util.keystr(path) else
            (jnp.ones_like(leaf) if "lambda_k" in jax.tree_util.keystr(path)
             else leaf), params)
    elif name == "d-skip":
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf * 0.0
            if jax.tree_util.keystr(path).endswith("['D']") else leaf, params)
    elif name == "restart":         # one document where there were three
        x = x.copy()
        x[:, 1] = np.where(x[:, 1] > 0, 1, 0)
    return program_loss(cfg, params, x)[0]


@pytest.mark.parametrize("name", ["window", "memory", "shared-keys-and-values",
                                  "lambda", "d-skip", "restart"])
def test_each_mechanism_matters(name, monkeypatch):
    """Switched off, each moves the loss by twenty times the tolerance the
    program is held to, and more: the comparison would catch its absence.
    The matrices are four times their start's N(0, 0.02), so that a mixer
    weighs in a loss that the embedding alone decides at these sizes (the
    least movement seen is 2.5e-4, the window's and the shared keys')."""
    params = jax.tree.map(
        lambda a: 4.0 * a if a.ndim > 1 and a.shape[0] != TINY.vocab_size
        else a, seeded(TINY))
    x = rows_of()
    whole = float(program_loss(TINY, params, x)[0])
    assert abs(whole - float(reference_loss(TINY, params, x))) <= LOSS_TOL
    assert abs(float(_without(name, params, x, monkeypatch)) - whole) \
        > 20 * LOSS_TOL


def test_a_lower_precision_fails_the_tolerances(monkeypatch):
    """bfloat16 where the configuration states float32 (the parameters; the
    scan's inputs and output) or where the rehearsal does (the large
    products' inputs) leaves some leaf's gradient ten times outside
    ``GRAD_TOL``, each of the three in turn. (The LOSS of this tiny model
    barely feels it, 1e-6: the embedding decides it. The gradients are what
    the comparison holds.)"""
    params, x = seeded(TINY), rows_of()
    with jax.default_matmul_precision("highest"):
        wants = jax.grad(lambda p: reference_loss(TINY, p, x))(params)

    def worst(cfg, params):
        grads = jax.grad(lambda p: program_loss(cfg, p, x)[0])(params)
        return max(float(jnp.abs(got - exact).max() / jnp.abs(exact).max())
                   for got, exact in zip(jax.tree.leaves(grads),
                                         jax.tree.leaves(wants)))

    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    assert worst(TINY, jax.tree.map(rounded, params)) > 10 * GRAD_TOL
    assert worst(dataclasses.replace(TINY, compute_dtype="bfloat16"),
                 params) > 10 * GRAD_TOL
    real = selective_scan.selective_scan
    monkeypatch.setattr(
        phi.scan, "selective_scan", lambda x, dl, a, b, c, run: rounded(
            real(*map(rounded, (x, dl, a, b, c)), run)))
    assert worst(TINY, params) > 10 * GRAD_TOL


# --------------------------------------------- (e) the vocabulary's slices
def test_the_slices_logits_side_by_side_are_the_uncut_models():
    """Four chips hold a quarter of the embedding's rows each. With ids drawn
    from the first slice (the cell's traffic), every chip computes the stack
    alike from the rows the first embeds (what the deployment exchanges, counted
    once) and its own slice of the logits with the program's own head; side
    by side they are the uncut reference's logits, and the first slice's loss
    is the program's."""
    whole = dataclasses.replace(TINY, vocab_size=512)
    params = seeded(whole)
    x = rows_of(vocab=128)
    slices = [{**params, "embed": params["embed"][k * 128:(k + 1) * 128]}
              for k in range(4)]

    def sliced_logits(p, rows, row):
        # the program's pieces, as ``sequence_stats`` strings them
        h, _ = phi.decoder(p["layers"], rows, row[1], TINY, jnp.float32)
        h = layers.layer_norm(h, p["final_norm"], p["final_norm_bias"],
                              TINY.layer_norm_eps)
        return lm_head._tied_chunk_loss(h, p["embed"], row[0],
                                        jnp.ones((T,)))[0]

    a_slice = jax.jit(sliced_logits)
    for row in map(jnp.asarray, x):
        rows = jnp.take(slices[0]["embed"], row[0], axis=0)
        with jax.default_matmul_precision("highest"):
            side_by_side = jnp.concatenate(
                [a_slice(p, rows, row) for p in slices], axis=1)
            want = ref.logits(params, row, ref_cfg(whole))
        assert side_by_side.shape == want.shape == (T, 512)
        assert float(jnp.abs(side_by_side - want).max()) <= 2e-5
    # the cell's program: the first slice alone, a smaller vocabulary
    loss, _ = program_loss(TINY, slices[0], x)
    assert abs(float(loss) - float(reference_loss(TINY, slices[0], x))) \
        <= LOSS_TOL


# --------------------------------------------------- sizes and refusals
def test_the_parameter_count_of_the_published_configuration():
    cfg = get_preset("phi4-mini-flash-l8").model
    shapes = jax.eval_shape(build_model(cfg)[0], jax.random.key(0))
    count_of = lambda tree: sum(int(np.prod(l.shape))
                                for l in jax.tree.leaves(tree))
    kinds = [kind for _, kind in phi.layer_kinds(cfg)]
    assert kinds == ["s6", "window", "s6", "window", "s6_memory", "full",
                     "gmu", "cross"]
    mixers = [count_of(layer["mixer"]) for layer in shapes["layers"]]
    norm = 2 * 2560
    assert mixers[0] == mixers[2] == mixers[4] == 41_241_600 + norm
    assert mixers[1] == mixers[3] == mixers[5] == 19_668_864 + norm
    assert mixers[6] == 26_214_400 + norm
    assert mixers[7] == 13_112_704 + norm
    assert all(count_of(layer["ffn"]) == 78_643_200 + norm
               for layer in shapes["layers"])
    assert count_of(shapes["embed"]) == 50_016 * 2560
    assert "head" not in shapes                         # tied
    assert count_of(shapes) == 979_332_096
    # l0 by the PUBLISHED index: layer 17's, not the sixth's
    assert abs(phi.lambda_init(17) - (0.8 - 0.6 * np.exp(-5.1))) < 1e-12


def test_the_start_of_the_mixers_is_the_one_the_file_assumes():
    params = build_model(TINY)[0](jax.random.key(0))
    mixer = params["layers"][0]["mixer"]
    assert np.allclose(np.exp(mixer["A_log"]), np.arange(1, 5)[None, :])
    assert np.all(np.asarray(mixer["D"]) == 1.0)
    step = np.asarray(jax.nn.softplus(mixer["dt_bias"]))
    assert 0.001 <= step.min() and step.max() <= 0.1 + 1e-6
    assert np.all(np.asarray(mixer["conv_b"]) == 0.0)
    attn = params["layers"][1]["mixer"]
    assert 0.02 < float(jnp.std(attn["lambda_q1"])) < 0.3
    assert np.all(np.asarray(attn["qkv_bias"]) == 0.0)


def test_the_scopes_of_a_tiny_round_name_this_stacks_pieces():
    """One walk of the compiled round's text: the pieces this stack brings
    are there, a Mamba-1 mixer's four and the memory unit inside ``ssm``, the
    combination inside ``attention``, the tied embedding's gradient inside
    ``embed``; the three attentions are modules around ``attention``; the
    scan's operations run forward, recomputed and backward."""
    from fedtpu.analysis.program import program_scopes
    from fedtpu.orchestration import loop
    from fedtpu.parallel.round import (LAYERS, MODULES, PIECES, RECOMPUTE,
                                       SERVER_UPDATE, SGD_PASS, STAGES)
    exp = build_experiment(tiny_phi4_flash())
    text = exp.make_step(1).lower(exp.state, exp.batch).compile().as_text()
    walk = program_scopes(
        text, STAGES + (loop.STATE_CHECK,), layers=LAYERS, pieces=PIECES,
        modules=MODULES, update=(SGD_PASS, SERVER_UPDATE),
        recompute=(RECOMPUTE,))
    found, pieces = walk["layers"], walk["pieces"]
    assert {"ssm", "attention", "dense_mlp", "lm_head_loss", "server_update",
            "embed"} <= set(found.values())
    assert {"s6_proj", "s6_conv", "s6_scan", "s6_gate", "gmu", "attn_core",
            "diff_combine", "tied_embed_grad", "sgd_pass"} <= set(
                pieces.values())
    assert {"attn_window", "attn_full", "attn_cross"} <= set(
        walk["modules"].values())
    inside = {"s6_proj": "ssm", "s6_conv": "ssm", "s6_scan": "ssm",
              "s6_gate": "ssm", "gmu": "ssm", "diff_combine": "attention",
              "tied_embed_grad": "embed"}
    for piece, layer in inside.items():
        of = [found[k] for k, p in pieces.items() if p == piece and k in found]
        assert of and of.count(layer) >= 0.9 * len(of), (piece, of)
    around = [found.get(k) for k in walk["modules"]]
    assert around.count("attention") >= 0.9 * len(around)
    passes = {walk["passes"].get(k, "forward") for k, piece in pieces.items()
              if piece == "s6_scan"}
    assert {"forward", "recompute", "backward"} <= passes


def test_the_scans_kernels_keep_the_scans_scope_and_count_their_positions(
        fused_scan_on_the_cpu, tmp_path):
    """The rule between the scan's bodies told yes and the kernels
    interpreted (``jax.checkpoint`` a pass-through: the interpreter's
    callbacks cannot stand under it), at an inner width of one lane tile and
    a state of one sublane tile: a tiny round LOWERED names
    ``s6_scan_forward`` and ``s6_scan_backward`` on its operations' name
    stacks, each under ``ssm/s6_scan`` and so the piece ``s6_scan`` (what
    ``p4_s6_scan_ms`` reads), the second in the backward pass; and RUN, it
    counts every scan position as one of the kernels', ``s6_fused_scan_
    positions`` = the rows' positions = ``s6_chunked_scan_positions``. (The
    compiled round at published widths holds the same of the Mosaic calls
    themselves: ``tests/test_aot_tpu_compile.py``.)"""
    import re

    from fedtpu.analysis.program import BACKWARD, _pass_of, _stage_of
    from fedtpu.parallel.round import LAYERS, PIECES

    sink = str(tmp_path / "ev.jsonl")
    cfg = tiny_phi4_flash(rounds=1,
                          telemetry=TelemetryConfig(events_path=sink))
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, hidden_size=64, mamba_d_state=8),
        data=dataclasses.replace(cfg.data, synthetic_rows=2),
        shard=dataclasses.replace(cfg.shard, num_clients=2))
    exp = build_experiment(cfg)
    text = exp.make_step(1).lower(exp.state, exp.batch).as_text(
        debug_info=True)
    names = set(re.findall(
        r'"([^"]*/s6_scan_(?:forward|backward)/[^"]*)"', text))
    kernels = {re.search(r"s6_scan_(?:forward|backward)", n).group(0)
               for n in names}
    assert kernels == {"s6_scan_forward", "s6_scan_backward"}
    for name in names:
        assert re.search(r"ssm\)*/s6_scan/s6_scan_(forward|backward)/", name)
        assert _stage_of(name, LAYERS) == "ssm", name
        assert _stage_of(name, PIECES) == "s6_scan", name
        assert (_pass_of(name, (), ()) == BACKWARD) == (
            "s6_scan_backward" in name), name
    run_experiment(cfg, verbose=False)
    counted = [json.loads(line) for line in open(sink)]
    counted = [e for e in counted
               if e["kind"] == "counters"][-1]["payload"]["counters"]
    assert counted["s6_positions"] == 2 * T * 3         # three Mamba-1 layers
    assert counted["s6_fused_scan_positions"] == 2 * T
    assert counted["s6_chunked_scan_positions"] == 2 * T
    assert counted["s6_fused_conv_positions"] == 0      # that rule: a CPU


def test_what_the_registry_refuses():
    def refused(match, **fields):
        with pytest.raises(ValueError, match=match):
            build_model(dataclasses.replace(TINY, **fields))

    refused("rising indices", layers_held=(0, 1, 9))
    refused("rising indices", layers_held=(1, 0))
    refused("no 's6_memory' layer before it", layers_held=(0, 1, 6))
    refused("no 'full' layer before it", layers_held=(0, 4, 7))
    refused("tied head only", tie_word_embeddings=False)
    refused("in pairs", num_attention_heads=2, num_key_value_heads=1)
    refused("no window", sliding_window=0)
    # a cut that holds the hinge and one period of each decoder is built
    held = dataclasses.replace(TINY, layers_held=(2, 3, 4, 5, 6, 7))
    assert [k for _, k in phi.layer_kinds(held)] == [
        "s6", "window", "s6_memory", "full", "gmu", "cross"]
