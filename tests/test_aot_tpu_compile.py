"""Ask the TPU's own compiler, without a chip, whether the kernels compile.

Interpret mode (every other Pallas test here) cannot see what Mosaic
refuses: a slice off the tiling, too much VMEM, a kernel that cannot be
partitioned. libtpu compiles for a chip that is described and not
attached (``jax.experimental.topologies``), so these tests hand the RDMA
ring with its synchronisation path on a four-device mesh to the v5e
compiler with ``interpret=False`` and look for the Mosaic custom call in
the compiled text. Nothing runs; a pass here is not a chip run.

The last case asks the same compiler what the ConvNet's training pass
moves through memory: the block order of ``fedtpu.models.convnet`` exists
for the bytes it does not write, and only the TPU's compiler shows them.

Named to sort early: tier-1 is cut by its clock, and a file late in the
alphabet guards nothing.
"""

from __future__ import annotations

import collections
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from fedtpu.parallel.ring_pallas import pallas_ring_all_reduce_sum

# The income model at the reference's widths (14 -> 50 -> 200 -> 2).
DIMS = (14, 50, 200, 2)
MODEL_SIZE = sum(a * b + b for a, b in zip(DIMS[:-1], DIMS[1:]))   # 11,352


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu here, or it cannot describe a v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc!r}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip (the next one warns and compiles
    again), so the cache is off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *args) -> str:
    lowered = jax.jit(fn).lower(*args)  # fedtpu: noqa[FTP006] one-shot AOT compile
    return lowered.compile().as_text()


def test_pallas_ring_sync_path_compiles_for_four_v5e_chips(topo):
    """interpret=False selects the barrier + capacity-semaphore protocol
    the interpreter never runs; the model's flat delta is the payload."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("clients",))

    def ring(t):
        return jax.shard_map(
            lambda u: pallas_ring_all_reduce_sum(u[0], "clients", 4,
                                                 interpret=False)[None],
            mesh=mesh, in_specs=P("clients"), out_specs=P("clients"))(t)

    x = jax.ShapeDtypeStruct((4, MODEL_SIZE), jnp.float32,
                             sharding=NamedSharding(mesh, P("clients")))
    text = _compiled_text(ring, x)
    assert "tpu_custom_call" in text


def _fusions_writing(text: str, dims) -> dict:
    """The entry computation's fusions with an output of these dimensions in
    any order (the compiler permutes them): name -> holds a convolution."""
    bodies = dict(re.findall(r"^(%fused_computation[\w.]*) .*?\{\n(.*?)^\}",
                             text, re.M | re.S))
    found = {}
    for name, shape, callee in re.findall(
            r"^\s*(%[\w.\-]+) = (.*?) fusion\(.*?calls=(%[\w.]+)",
            text[text.index("\nENTRY"):], re.M):
        outputs = (sorted(map(int, d.split(",")))
                   for d in re.findall(r"\w+\[([\d,]+)\]", shape))
        if sorted(dims) in outputs:
            found[name] = " convolution(" in bodies[callee]
    return found


def test_convnet_training_pass_keeps_one_full_resolution_copy(topo):
    """Pooling before bias and ReLU is worth what the compiled program no
    longer moves. 504 images a client, the benchmark's: the 264 MB first
    activation has to live in HBM as it does there (at 64 images it is 33 MB
    and the compiler keeps it on the chip, where the two orders are 15% apart
    and not 37%)."""
    from fedtpu.models.convnet import convnet_apply, convnet_init
    from tests.test_convnet import _masked_loss, old_order_apply

    clients, images = 8, 504
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct((clients,) + shape, dt,
                                                 sharding=one)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: convnet_init(jax.random.key(0), (32, 32, 3),
                                            (32, 64), 256, 10)))
    args = (params, sds((images, 32, 32, 3), jnp.float32),
            sds((images,), jnp.int32), sds((images,), jnp.float32))

    def compiled(apply):
        fn = jax.vmap(jax.value_and_grad(_masked_loss(apply, jnp.bfloat16)))
        return jax.jit(fn).lower(*args).compile()  # fedtpu: noqa[FTP006] one-shot AOT compile

    new, old = compiled(convnet_apply), compiled(old_order_apply)
    accessed = lambda c: c.cost_analysis()["bytes accessed"]
    assert accessed(new) <= 0.85 * accessed(old), (accessed(new), accessed(old))

    full = (clients, images, 32, 32, 32)
    new_full = _fusions_writing(new.as_text(), full)
    assert new_full and all(new_full.values()), new_full
    # The parser does see the copy where there is one: ReLU first writes it.
    assert not all(_fusions_writing(old.as_text(), full).values())


@pytest.fixture(scope="module")
def olmoe_round(topo):
    """``compiled(kernels)``: the shared-global round of the one-layer OLMoE
    preset (625.6M parameters, 8 clients, 16 packed 4,096-token sequences,
    FedAvgM) compiled for one described v5e chip, once with the XLA bodies
    of the attention core and of the expert matmuls and once with their
    Pallas bodies, as the chip picks them. The rules between the bodies see
    the CPU here and would pick the XLA bodies, so both cases steer the
    rules themselves."""
    from fedtpu.config import get_preset
    from fedtpu.models.registry import build_model
    from fedtpu.ops import grouped_matmul, packed_attention
    from fedtpu.ops.server_opt import make_server_optimizer
    from fedtpu.parallel.stateless import build_stateless_round_fn
    from fedtpu.training.task import build_task

    cfg = get_preset("olmoe-1b-7b-l1")
    mesh = Mesh(np.array(topo.devices[:1]), ("clients",))
    rep, by_client = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
    init_fn, stats_fn = build_model(cfg.model)
    server = make_server_optimizer("fedavgm", cfg.fed.server_lr,
                                   cfg.fed.server_momentum)
    params = jax.eval_shape(init_fn, jax.random.key(0))
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params)) == 625_616_896
    shaped = lambda tree, sharding: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)
    state = {"params": shaped(params, rep),
             "server_opt_state": shaped(jax.eval_shape(server.init, params), rep),
             "round": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
    seq = cfg.data.synthetic_features
    batch = {"x": jax.ShapeDtypeStruct((8, 8, 2, seq), jnp.int32, sharding=by_client),
             "y": jax.ShapeDtypeStruct((8, 8), jnp.int32, sharding=by_client),
             "mask": jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=by_client)}
    done = {}

    def compiled(fused: bool):
        if fused not in done:
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(packed_attention, "fused_attention_applies",
                              lambda q, k, v: fused)
                patch.setattr(grouped_matmul, "grouped_matmul_applies",
                              lambda xs, w: fused)
                step = build_stateless_round_fn(
                    mesh, build_task(cfg.model, stats_fn, cfg.model.vocab_size),
                    [1, 1, 2, 2, 2, 2, 3, 3],
                    learning_rate=cfg.optim.learning_rate, server_opt=server,
                    local_batch_rows=cfg.fed.local_batch_rows)
                done[fused] = step.lower(state, batch).compile()
        return done[fused]

    return compiled


# The shared-global round holds the model's forward and backward once a kind
# of step the clients' step counts call for (a client's only step, the first
# of several, one between, the last: fedtpu.parallel.stateless, PR 30), so
# what a step holds the fixture's round holds four times.
STEP_KINDS = 4


def _account(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _pallas_calls(compiled, scope: str) -> list:
    """The names of the compiled module's Pallas kernels under the named
    scope, one a call (the compiler's own ``ragged-dot-none`` kernels are
    ``tpu_custom_call``s too, and name no scope). A transform wraps the
    scope's component of the name (``transpose(jvp(experts))``)."""
    return re.findall(
        r'custom_call_target="tpu_custom_call".*op_name="[^"]*/(?:\w+\()*%s\)*/'
        r'(?:[^"/]*/)*?jit\((\w+)\)/[^"]*pallas_call"' % scope,
        compiled.as_text())


def _attention_kernels(compiled) -> dict:
    """How often each kernel of the tiled attention core
    (``fedtpu.ops.packed_attention``: called by name inside its jitted
    ``attention``, under the piece ``attn_core``, which a transform may
    wrap: ``jvp(attn_core)``) stands in the compiled module."""
    return dict(collections.Counter(re.findall(
        r'custom_call_target="tpu_custom_call".*op_name="[^"]*attn_core\)*/'
        r'jit\(attention\)/(\w+)/pallas_call"', compiled.as_text())))


def _attention_calls(forward: int, backward: int) -> dict:
    return {"packed_attention_forward": forward,
            "packed_attention_backward_dkv": backward,
            "packed_attention_backward_dq": backward}


def test_the_olmoe_round_at_published_widths_fits_one_v5e_chip(olmoe_round):
    """With the XLA attention body (what this compile picks by itself, and
    what the chip ran before PR 26) the round's account (arguments + outputs
    - aliased + temporaries) lies between 8 and 15.0 GB of the chip's 16:
    one global, the momentum that doubles as the delta accumulator, one
    client's copy, its gradient and a sequence's activations (13.80 GB,
    PERF.md section 4; 14.39 with the four kinds of step of PR 30)."""
    xla = olmoe_round(False)
    assert 8e9 <= _account(xla) <= 15.0e9, _account(xla)
    # global and momentum in place
    assert xla.memory_analysis().alias_size_in_bytes >= 5.0e9


def test_the_olmoe_round_with_fused_attention_drops_the_scores(olmoe_round):
    """Steered to the Pallas bodies, as the chip picks them, the same round
    holds the three attention kernels and no float32 16 x 4096^2 array:
    12.95 GB when this was written, 0.86 under the XLA bodies'; 12.98 and
    0.67 under since the head's own differentiation rule (PR 28); 12.80 and
    0.85 under since the grouped expert kernels (PR 29: no transposed copy
    of an expert weight, gradients of the expert matmuls in bf16); 11.73 and
    2.66 under since each gradient goes straight into the accumulator
    (PR 30)."""
    fused, xla = olmoe_round(True), olmoe_round(False)
    assert _account(fused) <= 13.1e9, _account(fused)
    assert _account(fused) <= _account(xla) - 0.6e9
    assert fused.memory_analysis().alias_size_in_bytes >= 5.0e9
    assert _attention_kernels(fused) == _attention_calls(
        forward=STEP_KINDS, backward=STEP_KINDS)
    assert not _attention_kernels(xla)


def test_the_olmoe_round_runs_its_experts_in_the_grouped_kernels(olmoe_round):
    """What says the Pallas body of the expert matmuls engaged: a layer's
    three grouped matmuls, their three input gradients (the same kernel on
    the weight in place) and their three weight gradients are nine Mosaic
    calls a kind of step under the experts' scope, and the compiler's own
    grouped kernel (``ragged-dot-none``) is nowhere; with the XLA body it is
    the other way round."""
    pallas, xla = olmoe_round(True), olmoe_round(False)
    assert sorted(_pallas_calls(pallas, "experts")) == (
        ["gmm"] * 6 * STEP_KINDS + ["tgmm"] * 3 * STEP_KINDS)
    assert "ragged-dot" not in pallas.as_text()
    assert not _pallas_calls(xla, "experts")
    assert xla.as_text().count(" custom-call(") and "ragged-dot-none" in xla.as_text()


PARAMETER_SHAPE = r"(?:1,)?(?:64,2048,1024|64,1024,2048|50304,2048|2048,50304)"


def test_the_olmoe_round_passes_over_the_parameters_once_a_step(olmoe_round):
    """PR 30, on the step counts ``[1, 1, 2, 2, 2, 2, 3, 3]``: a client's
    first step reads the global itself, so the compiled round copies no
    float32 array of a large parameter's shape (the parent copied the
    global into the steps' carry, two parameter sets moved a client). The
    first step's weights are invariant in the clients' loop, and the
    compiler would hoist their bf16 casts out of it, 1.05 GB alive all
    round, were the global not tied to the client's own step count: no bf16
    array of those shapes among the operands of the clients' ``while`` (the
    one ``main`` calls), and the account stays under the parent's
    12,802,246,144 + 1%."""
    fused = olmoe_round(True)
    text = fused.as_text()
    copies = re.findall(
        r"= f32\[%s\]\S* copy\(" % PARAMETER_SHAPE, text)
    assert not copies, copies
    entry = text[text.index("\nENTRY"):]
    clients = re.findall(r"= \((.*?)\) while\(", entry)
    assert len(clients) == 1                        # the scan over clients
    carried = re.findall(r"(\w+)\[%s\]" % PARAMETER_SHAPE, clients[0])
    assert carried and set(carried) == {"f32"}, carried
    assert _account(fused) <= 12.93e9, _account(fused)


@pytest.mark.parametrize("rows,groups,k,width,kernels", [
    (32768, 64, 2048, 1024, True), (32768 + 128, 64, 2048, 1024, False),
    (32768, 64, 2048, 1408, False), (8192, 8, 2688, 1856, True),
    (5632, 8, 3584, 1024, True), (5632, 8, 1024, 3584, True),
    (2816, 8, 2304, 1024, True), (2816, 8, 1024, 2304, True)])
def test_the_grouped_matmul_picks_its_body_by_shape_on_a_tpu(
        topo, monkeypatch, rows, groups, k, width, kernels):
    """The rule itself, told only that the backend is a TPU: at the
    benchmark's shapes (OLMoE's, a block of the hybrid stack's held
    experts, whose widths its tiles do not divide, and a block of the
    Xing4.0 and of the Kimi-Linear stack's, both products: the compile
    also holds each tile of the table to the kernel's 16 MB) a grouped
    matmul and its gradients compile to the three kernels and no
    ``ragged-dot``; at rows that are no whole tile, or a width there is no
    tile for, to ``ragged-dot`` and no such kernel."""
    from fedtpu.ops.grouped_matmul import grouped_matmul

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def with_gradients(xs, w, c, sizes):
        return jax.value_and_grad(
            lambda xs, w: (grouped_matmul(xs, w, sizes) * c).sum(),
            argnums=(0, 1))(xs, w)

    compiled = jax.jit(with_gradients).lower(  # fedtpu: noqa[FTP006] one-shot AOT compile
        sds((rows, k), jnp.bfloat16), sds((groups, k, width), jnp.bfloat16),
        sds((rows, width), jnp.float32), sds((groups,), jnp.int32)).compile()
    text = compiled.as_text()
    calls = re.findall(r'jit\((t?gmm)\)+/pallas_call', text)
    assert sorted(set(calls)) == (["gmm", "tgmm"] if kernels else [])
    assert len(calls) == (3 if kernels else 0)
    assert ("ragged-dot" in text) is not kernels


def _convolutions_over(text: str, dim: int) -> list:
    """The compiled module's convolutions (a matmul is one on this
    compiler) with ``dim`` among the dimensions of their result or of an
    operand, as ``[result, operands...]`` shapes."""
    found = []
    for body in text.split("\n\n"):
        shapes = dict(re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\])",
                                 body, re.M))
        for result, operands in re.findall(
                r"= (\w+\[[\d,]*\])\S* convolution\(([^)]*)\)", body):
            all_ = [result] + [shapes.get(o.split()[-1], o)
                               for o in operands.split(", ")]
            if any(str(dim) in re.findall(r"\d+", s) for s in all_):
                found.append(all_)
    return found


def test_the_olmoe_round_multiplies_over_the_vocabulary_three_times_a_chunk(
        olmoe_round):
    """The head's own differentiation rule in the compiled round: the
    logits, ``dlogits w^T`` and ``h^T dlogits`` and no second logits matmul
    (the checkpointed scan before PR 28 compiled to four) a kind of step,
    forward and backward rule under the head's scope. The account it has to
    stay in is the test's above."""
    text = olmoe_round(True).as_text()
    over_vocab = _convolutions_over(text, 50304)
    assert len(over_vocab) == 3 * STEP_KINDS, over_vocab
    assert sorted(c[0] for c in over_vocab) == sorted([
        "f32[2048,50304]", "f32[512,2048]", "f32[512,50304]"] * STEP_KINDS)
    assert "/jvp(lm_head_loss)/" in text
    assert "/transpose(jvp(lm_head_loss))/" in text


def test_fused_attention_core_compiles_for_v5e_without_the_scores(topo):
    """Forward and backward of the attention core on one published-width
    sequence (4096, 16 heads of 128): the fused body is three Mosaic
    kernels and under 300 MB of temporaries (53 MB when this was written)
    where the XLA body keeps over 2,000 (2,182: the scores, the masked
    scores, the probabilities)."""
    from fedtpu.ops.packed_attention import _fused_attention, _xla_attention

    one = SingleDeviceSharding(topo.devices[0])
    qkv = jax.ShapeDtypeStruct((4096, 16, 128), jnp.bfloat16, sharding=one)
    segs = jax.ShapeDtypeStruct((4096,), jnp.int32, sharding=one)

    def compiled(body):
        def with_gradients(q, k, v, w, segs):
            return jax.value_and_grad(
                lambda q, k, v: (body(q, k, v, segs) * w).sum(),
                argnums=(0, 1, 2))(q, k, v)
        return jax.jit(with_gradients).lower(  # fedtpu: noqa[FTP006] one-shot AOT compile
            qkv, qkv, qkv, qkv, segs).compile()

    fused, xla = compiled(_fused_attention), compiled(_xla_attention)
    assert _mosaic_calls(fused) == 3 and _mosaic_calls(xla) == 0
    assert fused.memory_analysis().temp_size_in_bytes < 300e6
    assert xla.memory_analysis().temp_size_in_bytes > 2000e6


@pytest.fixture(scope="module")
def hybrid_round(topo):
    """The shared-global round of the hybrid preset (``nemotron_h``: nine
    layers MEMEM*EME at published widths, 8 of 128 experts and an eighth of
    the vocabulary held, 667.0M parameters; 8 clients, 16 packed
    8,192-token sequences, FedAvgM) compiled for one described v5e chip.
    The rules between the bodies of the attention core, of the expert
    matmuls and of the mixers' two float32 passes read the process's
    backend, the CPU here: they are told it is a TPU and answer for the
    cell's shapes as they do on the chip."""
    from fedtpu.config import get_preset
    from fedtpu.models.registry import build_model
    from fedtpu.ops.server_opt import make_server_optimizer
    from fedtpu.parallel.stateless import build_stateless_round_fn
    from fedtpu.training.task import build_task

    cfg = get_preset("nemotron-h-30b-a3b-l9")
    mesh = Mesh(np.array(topo.devices[:1]), ("clients",))
    rep, by_client = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
    init_fn, stats_fn = build_model(cfg.model)
    server = make_server_optimizer("fedavgm", cfg.fed.server_lr,
                                   cfg.fed.server_momentum)
    params = jax.eval_shape(init_fn, jax.random.key(0))
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params)) == 666_963_456
    shaped = lambda tree, sharding: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)
    state = {"params": shaped(params, rep),
             "server_opt_state": shaped(jax.eval_shape(server.init, params), rep),
             "round": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
    seq = cfg.data.synthetic_features
    batch = {"x": jax.ShapeDtypeStruct((8, 8, 2, seq), jnp.int32, sharding=by_client),
             "y": jax.ShapeDtypeStruct((8, 8), jnp.int32, sharding=by_client),
             "mask": jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=by_client)}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step = build_stateless_round_fn(
            mesh, build_task(cfg.model, stats_fn, cfg.model.vocab_size),
            [1, 1, 2, 2, 2, 2, 3, 3],
            learning_rate=cfg.optim.learning_rate, server_opt=server,
            local_batch_rows=cfg.fed.local_batch_rows)
        return step.lower(state, batch).compile()


E_LAYERS = M_LAYERS = 4   # of the preset's nine, MEMEM*EME


def _named_kernels(compiled, piece: str) -> list:
    """``(kernel's name, the rest of its op_name before it)`` of the
    compiled module's Pallas kernels that are called by name under the scope
    ``piece`` (``pl.pallas_call(name=...)``: the name stands where a jitted
    library kernel's ``jit(...)`` does)."""
    return [(name, before) for before, name in re.findall(
        r'custom_call_target="tpu_custom_call".*op_name="([^"]*/%s)/(\w+)/'
        r'pallas_call"' % piece, compiled.as_text())]


def test_the_hybrid_round_at_published_widths_fits_one_v5e_chip(hybrid_round):
    """The round's account lies between the 8.0 GB the engine's 12 bytes a
    parameter come to and the 15.7 GB the configuration file states as its
    bound (15.4 when this was written; the chip's compiler allows 15.75
    GiB, 16.9 GB): global and momentum in place, one working copy, the
    layers' casts and one layer's intermediates. The compiler's scheduler
    fills what the chip has, so the account is near the bound by its own
    doing (PERF.md section 4)."""
    assert 8.0e9 <= _account(hybrid_round) <= 15.7e9, _account(hybrid_round)
    assert hybrid_round.memory_analysis().alias_size_in_bytes >= 5.3e9
    text = hybrid_round.as_text()
    # the one attention layer ran fused, a kind of step: the forward
    # kernel, once more in the layer's recomputation, and the two backward
    assert _attention_kernels(hybrid_round) == _attention_calls(
        forward=2 * STEP_KINDS, backward=STEP_KINDS)
    # the held experts ran in the grouped kernels (PR 33), an ``E`` layer
    # and kind of step: two products and, recomputed, two more, their two
    # input gradients (the same kernel on the weight in place) and their
    # two weight gradients; the compiler's own grouped kernel is nowhere
    assert sorted(_pallas_calls(hybrid_round, "experts")) == (
        ["gmm"] * 6 * E_LAYERS * STEP_KINDS + ["tgmm"] * 2 * E_LAYERS * STEP_KINDS)
    assert "ragged-dot" not in text
    # and these, with the mixers' six a layer (PR 36, the test below), are
    # all the program's Mosaic calls
    assert _mosaic_calls(hybrid_round) == STEP_KINDS * (
        4 + 8 * E_LAYERS + 6 * M_LAYERS)
    # every scope the reducers read is in the program
    for scope in ("ssm", "ssm_scan", "shared_expert", "router",
                  "expert_dispatch", "experts", "lm_head_loss"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope


@pytest.mark.parametrize("piece,forward,backward", [
    ("ssm_conv", "ssm_conv_forward", "ssm_conv_backward"),
    ("ssm_gate_norm", "ssm_gate_norm_forward", "ssm_gate_norm_backward")])
def test_the_hybrid_round_runs_the_mixers_passes_in_the_tiled_kernels(
        hybrid_round, piece, forward, backward):
    """PR 36, the rule told the backend is a TPU: each of a state-space
    mixer's two float32 passes is one Mosaic call forward, the same once
    more in the layer's recomputation, and one backward, an ``M`` layer and
    kind of step; each call stands under its piece's scope (what
    ``ssm_conv_ms`` / ``ssm_gate_norm_ms`` read) and its ``op_name`` tells
    the direction as ``analysis.program`` reads it: nothing, remat's
    ``rematted_computation``, a ``transpose(``."""
    from fedtpu.analysis.program import (BACKWARD, FORWARD, RECOMPUTE,
                                         _pass_of, _stage_of)
    from fedtpu.parallel.round import PIECES

    calls = _named_kernels(hybrid_round, piece)
    each = M_LAYERS * STEP_KINDS
    assert sorted(name for name, _ in calls) == (
        [backward] * each + [forward] * 2 * each)
    directions = {FORWARD: 0, RECOMPUTE: 0, BACKWARD: 0}
    for name, before in calls:
        op_name = f"{before}/{name}/pallas_call"
        assert _stage_of(op_name, PIECES) == piece
        direction = _pass_of(op_name, (), ())
        assert (direction == BACKWARD) == (name == backward), op_name
        directions[direction] += 1
    assert directions == {FORWARD: each, RECOMPUTE: each, BACKWARD: each}


def _one_step_kind_round(topo, preset: str, parameters: int):
    """The shared-global round of a language-model preset whose clients take
    ONE kind of step (8 clients, the preset's 16 packed sequences of 4,096
    tokens, FedAvgM) compiled for one described v5e chip, the rules between
    the bodies told the backend is a TPU as for the hybrid round."""
    from fedtpu.config import get_preset
    from fedtpu.models.registry import build_model
    from fedtpu.ops.server_opt import make_server_optimizer
    from fedtpu.parallel.stateless import build_stateless_round_fn
    from fedtpu.training.task import build_task

    cfg = get_preset(preset)
    mesh = Mesh(np.array(topo.devices[:1]), ("clients",))
    rep, by_client = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
    init_fn, stats_fn = build_model(cfg.model)
    server = make_server_optimizer("fedavgm", cfg.fed.server_lr,
                                   cfg.fed.server_momentum)
    params = jax.eval_shape(init_fn, jax.random.key(0))
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params)) == parameters
    shaped = lambda tree, sharding: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)
    state = {"params": shaped(params, rep),
             "server_opt_state": shaped(jax.eval_shape(server.init, params), rep),
             "round": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
    from fedtpu.data.tokens import skewed_sizes

    seq = cfg.data.synthetic_features
    sizes = [int(n) for n in skewed_sizes(cfg.data.synthetic_rows, 8)]
    assert sizes == [1, 1, 2, 2, 2, 2, 3, 3] and cfg.fed.one_step_kind
    longest = max(sizes)
    batch = {"x": jax.ShapeDtypeStruct((8, longest, 2, seq), jnp.int32, sharding=by_client),
             "y": jax.ShapeDtypeStruct((8, longest), jnp.int32, sharding=by_client),
             "mask": jax.ShapeDtypeStruct((8, longest), jnp.float32, sharding=by_client)}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step = build_stateless_round_fn(
            mesh, build_task(cfg.model, stats_fn, cfg.model.vocab_size), sizes,
            learning_rate=cfg.optim.learning_rate, server_opt=server,
            local_batch_rows=cfg.fed.local_batch_rows,
            one_step_kind=cfg.fed.one_step_kind)
        return step.lower(state, batch).compile()


@pytest.fixture(scope="module")
def xing4_round(topo):
    """The round of the four-stream preset (``xing4``: one dense and four
    expert layers and the multi-token-prediction module at published widths,
    8 of 64 experts and an eighth of the vocabulary held, 913.5M
    parameters). With all four kinds of step, were each a trace of its own,
    that round is refused at 16.39 GiB of the chip's 15.75 (PERF.md section
    6, PR 37)."""
    return _one_step_kind_round(topo, "xing4-29b-a4b-l5-mtp1", 913_473_668)


X4_BLOCKS = 6               # five layers and the prediction module's block
X4_EXPERT_BLOCKS = 5        # four of the layers and the module's block
X4_STEP_KINDS = 1           # every step from the working copy: one trace
# The four-stream round's account with the residual modules' passes in their
# kernels and the held experts in the grouped ones (the compiler's own peak,
# this file's compile for a described v5e).
XING4_ROUND_ACCOUNT = 14_793_590_784


def test_the_four_stream_round_at_published_widths_fits_one_v5e_chip(xing4_round):
    """The round's account (the compiler's own peak: for this program the
    sum of the parts reads above the chip's memory,
    ``train_xing4.program_account``) lies between the 10.96 GB the engine's
    12 bytes a parameter come to and the bound the configuration file states,
    and is this file's ``XING4_ROUND_ACCOUNT`` to a thousandth of a percent
    (the configuration file is the benchmark's and states PR 37's
    15,146,139,136; with the residual modules' passes in the kernels of PR 41
    the streams' layout copies and the cotangents' sums are no arrays and
    the compile reads 228 MB less, with the held experts in the grouped
    kernels of PR 45 no transposed copy of an expert weight is one and it
    reads 124 MB less again; the chip's compiler allows 15.75 GiB,
    16.9 GB); global and momentum in place. Every block's
    attention ran the tiled core at the padded head, a kind of step: the
    forward kernel, once more in the block's recomputation, and the two
    backward; the held experts ran in the grouped kernels at the tiles
    measured for 3,584 x 1,024 (PR 45), an expert block and kind of step:
    three products forward, the same in the block's recomputation (the
    residual module's write reads their sum) and once more in the experts'
    own backward pass, their three input gradients and their three weight
    gradients, and the compiler's own grouped kernel is nowhere; every scope
    the reducers read is in the program."""
    import json
    import os

    from perfbench.drivers.train_xing4 import program_account

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs",
                           "xing4-29b-a4b-l5-mtp1-fed8.json")) as fh:
        memory = json.load(fh)["memory"]
    account = program_account(xing4_round.memory_analysis())
    assert account["peak"] > 0 and account["total"] == account["peak"]
    assert 10.96e9 <= account["total"] <= memory[
        "round_account_bound_bytes"] == 15.7e9, account
    assert memory["round_account_bytes"] == 15_146_139_136
    assert abs(account["total"] - XING4_ROUND_ACCOUNT) <= (
        1e-5 * XING4_ROUND_ACCOUNT), account
    assert account["aliased"] >= 7.3e9
    text = xing4_round.as_text()
    each = X4_BLOCKS * X4_STEP_KINDS
    assert _attention_kernels(xing4_round) == _attention_calls(
        forward=2 * each, backward=each)
    assert re.search(r"bf16\[32,4096,256\]", text)      # q, k, v at one width
    experts = X4_EXPERT_BLOCKS * X4_STEP_KINDS
    assert sorted(_pallas_calls(xing4_round, "experts")) == (
        ["gmm"] * 12 * experts + ["tgmm"] * 3 * experts)
    assert "ragged-dot" not in text
    for scope in ("attention", "attn_core", "attn_latent", "hyper_conn",
                  "hc_sinkhorn", "dense_mlp", "shared_expert", "router",
                  "expert_dispatch", "experts", "mtp", "mtp_proj",
                  "lm_head_loss", "embed", "sgd_pass", "server_update"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope


def test_the_four_stream_round_runs_its_residual_modules_in_the_tiled_kernels(
        xing4_round):
    """PR 41, the rule told the backend is a TPU: a residual module's passes
    over the streams are Mosaic calls of ``fedtpu.ops.hyper_conn``, two a
    direction: ``mix_read`` and ``write`` forward, the same once more in the
    block's recomputation (but for the write of a block's SECOND module,
    whose result no backward pass reads: the compiler drops it) and their two
    transposes; each call stands under ``hyper_conn`` and in no piece (what
    ``x4_hyper_conn_ms`` reads) and its ``op_name`` tells the direction as
    ``analysis.program`` reads it; no array of the streams' shape is copied
    or transposed around them."""
    from fedtpu.analysis.program import (BACKWARD, FORWARD, RECOMPUTE,
                                         _pass_of, _stage_of)
    from fedtpu.parallel.round import LAYERS, PIECES

    text = xing4_round.as_text()
    calls = re.findall(
        r'custom_call_target="tpu_custom_call".*op_name="([^"]*hyper_conn\)*/'
        r'hyper_conn_(\w+)/pallas_call)"', text)
    modules = 2 * X4_BLOCKS * X4_STEP_KINDS
    found = collections.Counter()
    for op_name, kernel in calls:
        assert _stage_of(op_name, LAYERS) == "hyper_conn", op_name
        assert _stage_of(op_name, PIECES) is None, op_name
        direction = _pass_of(op_name, (), ())
        assert (direction == BACKWARD) == kernel.endswith("_backward"), op_name
        found[kernel, direction] += 1
    assert found == {
        ("mix_read_forward", FORWARD): modules,
        ("write_forward", FORWARD): modules,
        ("mix_read_forward", RECOMPUTE): modules,
        ("write_forward", RECOMPUTE): modules // 2,
        ("mix_read_backward", BACKWARD): modules,
        ("write_backward", BACKWARD): modules}, found      # 66 in all
    assert not re.search(r"= f32\[4,4096,3584\]\S* (?:copy|transpose)\(", text)


KDA_LAYERS = 4              # of the preset's five, K K K F K
KIMI_EXPERT_LAYERS = 4      # every layer after the leading dense one
# The delta-rule round's account with the recurrence in its kernels and the
# held experts in the grouped ones (the compiler's own peak, this file's
# compile for a described v5e).
KIMI_ROUND_ACCOUNT = 9_230_271_488


@pytest.fixture(scope="module")
def kimi_linear_round(topo):
    """The round of the delta-rule preset (``kimi_linear``: the first five
    layers of Kimi-Linear-48B-A3B at published widths, four KDA mixers and
    one latent-attention layer, 8 of 256 experts and an eighth of the
    vocabulary held, 602.5M parameters)."""
    return _one_step_kind_round(topo, "kimi-linear-48b-a3b-l5", 602_450_816)


def test_the_delta_rule_round_at_published_widths_fits_one_v5e_chip(
        kimi_linear_round):
    """The round's account (the compiler's own peak, as the four-stream
    round's) lies between the 7.23 GB the engine's 12 bytes a parameter come
    to and the bound the configuration file states (over 4.3 GB, under 15.0:
    ISSUE 39), and is what the file's ``memory`` states to a thousandth of a
    percent; global and momentum in place. The one attention layer ran the
    tiled core at the padded head (the forward kernel, once more in the
    layer's recomputation, and the two backward); the held experts ran in
    the grouped kernels at the tiles measured for 2,304 x 1,024 (PR 45), an
    expert layer: three products forward and once more in the experts' own
    backward pass (the layer's recomputation needs no sum of theirs and the
    compiler drops them there), their three input gradients and their three
    weight gradients, and the compiler's own grouped kernel is nowhere; every
    scope the reducers read is in the program."""
    import json
    import os

    from perfbench.drivers.train_xing4 import program_account

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs",
                           "kimi-linear-48b-a3b-l5-fed8.json")) as fh:
        memory = json.load(fh)["memory"]
    account = program_account(kimi_linear_round.memory_analysis())
    assert account["peak"] > 0 and account["total"] == account["peak"]
    assert 4.3e9 < 7.23e9 <= account["total"] <= memory[
        "round_account_bound_bytes"] == 15.0e9, account
    # the file is the benchmark's and states PR 39's account, the recurrence
    # in its XLA form; in the kernels of PR 40 its scores, triangular
    # inverses and chunk products are no arrays, and the compile reads
    # 2.06 GB less; with the held experts in the grouped kernels of PR 45
    # 158 MB less again
    assert memory["round_account_bytes"] == 11_445_461_504
    assert abs(account["total"] - KIMI_ROUND_ACCOUNT) <= (
        1e-5 * KIMI_ROUND_ACCOUNT), account
    assert account["aliased"] >= 4.8e9
    text = kimi_linear_round.as_text()
    assert _attention_kernels(kimi_linear_round) == _attention_calls(
        forward=2, backward=1)
    assert re.search(r"bf16\[32,4096,256\]", text)      # q, k, v at one width
    assert sorted(_pallas_calls(kimi_linear_round, "experts")) == (
        ["gmm"] * 9 * KIMI_EXPERT_LAYERS + ["tgmm"] * 3 * KIMI_EXPERT_LAYERS)
    assert "ragged-dot" not in text
    for scope in ("kda", "kda_scan", "kda_in_proj", "kda_conv", "kda_gates",
                  "kda_out_proj", "attention", "attn_core", "attn_latent",
                  "dense_mlp", "shared_expert", "router", "expert_dispatch",
                  "experts", "lm_head_loss", "embed", "sgd_pass",
                  "server_update"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope


def test_the_delta_rule_round_runs_its_recurrences_in_the_tiled_kernels(
        kimi_linear_round):
    """PR 40, the rule told the backend is a TPU: a KDA layer's recurrence
    is one Mosaic call forward, the same once more in the layer's
    recomputation (there it writes the chunks' entering states too), and one
    backward; each call stands under ``kda/kda_scan`` (what ``kl_kda_scan_ms``
    reads: the layer ``kda_scan``, no piece) and its ``op_name`` tells the
    direction as ``analysis.program`` reads it."""
    from fedtpu.analysis.program import (BACKWARD, FORWARD, RECOMPUTE,
                                         _pass_of, _stage_of)
    from fedtpu.parallel.round import LAYERS, PIECES

    calls = _named_kernels(kimi_linear_round, "kda_scan")
    assert sorted(name for name, _ in calls) == (
        ["kda_scan_backward"] * KDA_LAYERS + ["kda_scan_forward"] * 2 * KDA_LAYERS)
    directions = {FORWARD: 0, RECOMPUTE: 0, BACKWARD: 0}
    for name, before in calls:
        op_name = f"{before}/{name}/pallas_call"
        assert "kda/kda_scan/" in op_name or "kda)/kda_scan/" in op_name, op_name
        assert _stage_of(op_name, LAYERS) == "kda_scan"
        assert _stage_of(op_name, PIECES) is None
        direction = _pass_of(op_name, (), ())
        assert (direction == BACKWARD) == (name == "kda_scan_backward"), op_name
        directions[direction] += 1
    assert directions == dict.fromkeys((FORWARD, RECOMPUTE, BACKWARD), KDA_LAYERS)
    # no (chunks, heads, C, C) plane of scores is an array of the program
    assert "f32[64,32,64,64]" not in kimi_linear_round.as_text()


S6_LAYERS = 3               # of the preset's eight: layers 0, 2 and 16
# The decoder-hybrid-decoder round's account with the selective scan in its
# kernels (the compiler's own peak, this file's compile for a described v5e).
PHI4_ROUND_ACCOUNT = 13_704_988_160


@pytest.fixture(scope="module")
def phi4_flash_round(topo):
    """The round of the decoder-hybrid-decoder preset (``phi4_flash``: eight
    layers of Phi-4-mini-flash-reasoning at published widths, three Mamba-1
    mixers, two window and one full differential attention, a Gated Memory
    Unit and cross-attention, a quarter of the vocabulary, the head tied to
    the embedding, 979.3M parameters)."""
    return _one_step_kind_round(topo, "phi4-mini-flash-l8", 979_332_096)


def test_the_decoder_hybrid_decoder_round_at_published_widths_fits_one_v5e_chip(
        phi4_flash_round):
    """The round's account (the compiler's own peak) lies between the 11.75
    GB the engine's 12 bytes a parameter come to and the bound the
    configuration file states (15.7 GB: ISSUE 44), with a QUARTER of the
    vocabulary held, and is what the file's ``memory`` states to a
    thousandth of a percent; global and momentum in place. The four
    attention layers ran the tiled core, two calls a layer (the forward
    kernel, once more in the layer's recomputation, and the two backward),
    at the padded head (64 | 128 to 128); the three Mamba-1 convolutions ran
    the hybrid stack's tiled kernel; every scope the reducers read is in the
    program. And no array of the program is as large as one row's scan
    states, ``(4096, 5120, 16)``, forward or backward, nor as a block's
    states of all channels (256 positions), nor is the XLA body's chunk of 64
    there: in the kernels a state never leaves the chip's own memory (PR
    46)."""
    import json
    import math
    import os

    from perfbench.drivers.train_xing4 import program_account

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs",
                           "phi4-mini-flash-l8-fed8.json")) as fh:
        memory = json.load(fh)["memory"]
    account = program_account(phi4_flash_round.memory_analysis())
    assert account["peak"] > 0 and account["total"] == account["peak"]
    assert memory["engine_bytes"] == 12 * 979_332_096
    assert memory["engine_bytes"] <= account["total"] <= memory[
        "round_account_bound_bytes"] == 15.7e9, account
    # the file is the benchmark's and states PR 44's account, the scan in its
    # XLA form; in the kernels of PR 46 a chunk's state-sized arrays and the
    # chunks' entering states are no arrays, and the compile reads 99 MB less
    assert memory["round_account_bytes"] == 13_804_177_920
    assert abs(account["total"] - PHI4_ROUND_ACCOUNT) <= (
        1e-5 * PHI4_ROUND_ACCOUNT), account
    assert account["aliased"] >= 7.8e9
    text = phi4_flash_round.as_text()
    assert _attention_kernels(phi4_flash_round) == _attention_calls(
        forward=2 * 2 * 4, backward=2 * 4)
    assert re.search(r"bf16\[20,4096,128\]", text)     # q, k, v at one width
    convs = collections.Counter(
        name for name, _ in _named_kernels(phi4_flash_round, "s6_conv"))
    assert convs == {"ssm_conv_forward": 6, "ssm_conv_backward": 3}
    for scope in ("ssm", "s6_proj", "s6_conv", "s6_scan", "s6_gate", "gmu",
                  "attention", "attn_core", "attn_window", "attn_full",
                  "attn_cross", "diff_combine", "dense_mlp", "lm_head_loss",
                  "embed", "tied_embed_grad", "sgd_pass", "server_update"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    states = 4096 * 5120 * 16
    shapes = {tuple(map(int, shape.split(",")))
              for shape in re.findall(r"(?:f32|bf16)\[([0-9,]+)\]", text)}
    largest = max(map(math.prod, shapes))
    assert largest == 50_016 * 2560 < states, largest   # the embedding
    # what is shaped as states (an axis of 16 beside all 5,120 channels):
    # the blocks' entering states, one a block of 256 positions, and nothing
    # as large as the XLA body's chunk of 64 positions
    stately = {shape for shape in shapes
               if len(shape) > 2 and 16 in shape and 5120 in shape}
    assert (16, 16, 5120) in stately, stately
    assert max(map(math.prod, stately)) < 64 * 16 * 5120, stately
    assert "f32[16,4,16,5120]" not in text  # the XLA body's chunk: 64 positions


def test_the_decoder_hybrid_decoder_round_runs_its_scans_in_the_tiled_kernels(
        phi4_flash_round):
    """PR 46, the rule told the backend is a TPU: a Mamba-1 layer's selective
    scan is one Mosaic call forward, the same once more in the layer's
    recomputation (there it writes the blocks' entering states too), and one
    backward; each call stands under ``ssm/s6_scan`` (what ``p4_s6_scan_ms``
    reads: the piece ``s6_scan`` of the layer ``ssm``) and its ``op_name``
    tells the direction as ``analysis.program`` reads it."""
    from fedtpu.analysis.program import (BACKWARD, FORWARD, RECOMPUTE,
                                         _pass_of, _stage_of)
    from fedtpu.parallel.round import LAYERS, PIECES

    calls = _named_kernels(phi4_flash_round, "s6_scan")
    assert collections.Counter(name for name, _ in calls) == {
        "s6_scan_forward": 2 * S6_LAYERS, "s6_scan_backward": S6_LAYERS}
    directions = {FORWARD: 0, RECOMPUTE: 0, BACKWARD: 0}
    for name, before in calls:
        op_name = f"{before}/{name}/pallas_call"
        assert "ssm/s6_scan/" in op_name or "ssm)/s6_scan/" in op_name, op_name
        assert _stage_of(op_name, LAYERS) == "ssm"
        assert _stage_of(op_name, PIECES) == "s6_scan"
        direction = _pass_of(op_name, (), ())
        assert (direction == BACKWARD) == (name == "s6_scan_backward"), op_name
        directions[direction] += 1
    assert directions == dict.fromkeys((FORWARD, RECOMPUTE, BACKWARD),
                                       S6_LAYERS)


SOLAR_KDA_LAYERS = 3        # of the preset's four, G K K K
# The Solar-Open2 round's account (the compiler's own peak, this file's
# compile for a described v5e): 16 of 64 heads a mixer, 8 of 320 experts.
SOLAR_ROUND_ACCOUNT = 13_454_897_152


@pytest.fixture(scope="module")
def solar_open2_round(topo):
    """The round of the Solar-Open2 preset (``solar_open2``: the first four
    layers of Solar-Open2-250B at published widths, a gated grouped-query
    layer and three KDA mixers whose step runs to 2, 8 of 320 experts, 16 of
    64 heads and an eighth of the vocabulary held, 905.8M parameters)."""
    return _one_step_kind_round(topo, "solar-open2-250b-l4", 905_766_576)


def test_the_solar_open2_round_at_published_widths_fits_one_v5e_chip(
        solar_open2_round):
    """The round's account (the compiler's own peak) lies between the 10.87
    GB the engine's 12 bytes a parameter come to and the bound the
    configuration file states (over 4.0 GB, under 15.0: ISSUE 47), and is
    what the file's ``memory`` states to a thousandth of a percent; global
    and momentum in place. The softmax layer ran the tiled core at its
    unpadded 128 head; the recurrences ran in their kernels, a call forward,
    once more in the layer's recomputation and one backward a KDA layer; the
    held experts have no measured tiles at 4,096 x 1,280 and run the
    compiler's own grouped kernel (the finding a later ``perf_opt`` starts
    from); the gate's scope and every scope the reducers read is in the
    program."""
    import json
    import os

    from perfbench.drivers.train_xing4 import program_account

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "perfbench", "configs",
                           "solar-open2-250b-l4-fed8.json")) as fh:
        memory = json.load(fh)["memory"]
    account = program_account(solar_open2_round.memory_analysis())
    assert account["peak"] > 0 and account["total"] == account["peak"]
    assert (memory["round_account_floor_bytes"] == 4.0e9 < 10.87e9
            <= account["total"] < memory["round_account_bound_bytes"]
            == 15.0e9), account
    assert memory["round_account_bytes"] == SOLAR_ROUND_ACCOUNT
    assert abs(account["total"] - SOLAR_ROUND_ACCOUNT) <= (
        1e-5 * SOLAR_ROUND_ACCOUNT), account
    assert account["aliased"] >= 7.2e9
    text = solar_open2_round.as_text()
    assert _attention_kernels(solar_open2_round) == _attention_calls(
        forward=2, backward=1)
    assert re.search(r"bf16\[16,4096,128\]", text)      # q, k, v: 16 heads of 128
    calls = collections.Counter(
        name for name, _ in _named_kernels(solar_open2_round, "kda_scan"))
    assert calls == {"kda_scan_forward": 2 * SOLAR_KDA_LAYERS,
                     "kda_scan_backward": SOLAR_KDA_LAYERS}
    assert _pallas_calls(solar_open2_round, "experts") == []
    assert "ragged-dot" in text
    for scope in ("kda", "kda_scan", "kda_in_proj", "kda_conv", "kda_gates",
                  "kda_out_proj", "attention", "attn_core", "attn_gate",
                  "shared_expert", "router", "expert_dispatch", "experts",
                  "lm_head_loss", "embed", "sgd_pass", "server_update"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    assert "attn_latent" not in text and "dense_mlp" not in text
