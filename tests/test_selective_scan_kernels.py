"""The Mamba-1 selective scan's tiled kernels (fedtpu.ops.selective_scan),
interpreted on the CPU, against the two things that say what they compute:
the chunked XLA form ``chunked_selective_scan`` (the definition, and the body
wherever the kernels do not exist) and the token-by-token ``plain_scan``.
Values and the gradient of every input in float32 at the tolerance the
definition's own test holds, over the hard rows; a document behind another;
what a bfloat16 state would do; the rule between the two bodies."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from fedtpu.ops import selective_scan as scan
from fedtpu.ops import ssm_passes

# rows of two blocks of 128 positions: a state and its cotangent cross an edge
T, BLOCK, TILE, N = 256, 128, 128, 16
TOL = 5e-6


def _scan_inputs(segs, d=TILE, seed=0, step=-3.0, decay=1.0):
    """What a mixer hands its scan (``tests.test_phi4_flash._scan_inputs`` at
    the kernels' widths): ``x`` after a SiLU, steps after a softplus around
    ``softplus(step)``, ``A = -(1 .. N) decay``."""
    t = len(segs)
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.nn.silu(jax.random.normal(ks[0], (t, d)))
    dl = jax.nn.softplus(2.0 * jax.random.normal(ks[1], (t, d)) + step)
    a = -decay * jnp.broadcast_to(jnp.arange(1.0, N + 1), (d, N)) * jnp.exp(
        0.1 * jax.random.normal(ks[2], (d, N)))
    b, c = (jax.random.normal(k, (t, N)) for k in ks[3:5])
    run, _ = ssm_passes.document_runs(jnp.asarray(segs, jnp.int32))
    return (x, dl, a, b, c), run, jax.random.normal(ks[5], (t, d))


BODIES = {
    "kernels": lambda *a: scan.fused_selective_scan(*a, BLOCK, TILE),
    "definition": lambda *a: scan.chunked_selective_scan(*a, 64),
    "token by token": scan.plain_scan}


@functools.lru_cache(maxsize=None)
def _program(body: str):
    """``(inputs..., run, weigh) -> (out, the gradient of its weighted sum by
    each input)`` of one body, ONE jitted program (the interpreter is driven
    under ``jax.jit`` alone) that every case of a shape shares: a case is
    then a run of it, half the seconds of tracing it."""
    def total(x, dl, a, b, c, run, weigh):
        out = BODIES[body](x, dl, a, b, c, run)
        return (out * weigh).sum(), out

    both = jax.jit(jax.value_and_grad(total, argnums=range(5), has_aux=True))

    def program(inputs, run, weigh):
        with jax.default_matmul_precision("highest"):
            (_, out), gradients = both(*inputs, run, weigh)
        return out, gradients
    return program


@functools.lru_cache(maxsize=None)
def _kernels():
    return jax.jit(BODIES["kernels"])


SEVERAL = [1] * 37 + [2] * 60 + [3] * 71 + [4] * 57 + [0] * 31
HARD_ROWS = {
    "a-start-at-a-blocks-first-position": [1] * 128 + [2] * 128,
    "a-start-at-a-blocks-last-position": [1] * 127 + [2] * 129,
    "starts-inside-blocks": SEVERAL,
    "two-one-token-documents": [1] * 100 + [2] + [3] + [4] * 154,
    "one-document": [1] * T,
    "a-padded-tail": [1] * 90 + [2] * 70 + [0] * 96,
}


@pytest.mark.parametrize("case,d,step,decay", [
    *((name, TILE, -3.0, 1.0) for name in HARD_ROWS),
    ("starts-inside-blocks", 2 * TILE, -3.0, 1.0),
    ("starts-inside-blocks", TILE, 6.0, 1.0),
    ("one-document", TILE, -8.0, 0.01)],
    ids=[*HARD_ROWS, "two-channel-tiles", "decays-that-underflow",
         "hardly-any-decay"])
def test_the_kernels_are_the_definition_and_the_token_by_token_scan(
        case, d, step, decay):
    """Values and the gradient of each of x, dl, A, B and C, float32, against
    the XLA form AND ``plain_scan`` at 5e-6 of the values' and of a
    gradient's scale (the order of the sums over ``N`` and over the channels
    differs and nothing else; the largest gaps seen are 3e-7 and 7e-7):
    documents that start at a block's first position, at its last and inside
    one, two documents of one token side by side, one document a row (the
    state and its cotangent cross the blocks' edge), a padded tail (one run
    of its own, as ``document_runs`` has it), two channel tiles (the sums
    over the channels add
    up over tiles), steps near 6 with ``A`` down to -16 (decays that
    underflow: ``exp(-96)``) and steps of 3e-4 under ``A`` of a hundredth
    (a state that hardly decays over 256 positions)."""
    inputs, run, weigh = _scan_inputs(HARD_ROWS[case], d, step=step,
                                      decay=decay)
    with pltpu.force_tpu_interpret_mode():
        ours, ours_d = _program("kernels")(inputs, run, weigh)
    for name in ("definition", "token by token"):
        theirs, theirs_d = _program(name)(inputs, run, weigh)
        scale = max(float(jnp.abs(theirs).max()), 1.0)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                                   rtol=0, atol=TOL * scale, err_msg=name)
        for leaf, got, exact in zip(("x", "dl", "A", "B", "C"), ours_d,
                                    theirs_d):
            assert bool(jnp.isfinite(got).all()), leaf
            scale = float(jnp.abs(exact).max())
            assert scale > 1e-3, leaf                       # it is reached
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(exact), rtol=0,
                atol=TOL * max(scale, 1e-30), err_msg=f"{name} d{leaf}")


def test_a_document_packed_behind_another_scans_as_it_does_alone():
    """The state is zero at a document's first token, in the kernels as in
    the definition: the second document's outputs are those of the document
    alone at the row's start (the same block edges cut it elsewhere), and a
    restart that is left out changes them."""
    both, run, _ = _scan_inputs([1] * 128 + [2] * 128)
    alone = tuple(a[128:] if a.shape[0] == T else a for a in both)
    with pltpu.force_tpu_interpret_mode():
        packed = _kernels()(*both, run)[128:]
        single = _kernels()(*alone, run[128:] - 1)
        merged = _kernels()(*both, jnp.ones_like(run))[128:]
    assert float(jnp.abs(packed - single).max()) <= 2e-6
    assert float(jnp.abs(merged - single).max()) > 1e-2


def test_a_bfloat16_state_is_told_apart_at_ten_tolerances(monkeypatch):
    """A lower precision fails: with the state a block hands the next rounded
    to bfloat16 (twice in this row of three blocks, a length no other test
    runs: a kernel traced before the patch is not taken from a cache) the
    float32 comparison fails ten times over, and more."""
    inputs, run, _ = _scan_inputs([1] * (3 * BLOCK))
    store = scan._store_state

    def rounded(ref, i, state):
        store(ref, i, [h.astype(jnp.bfloat16).astype(jnp.float32)
                       for h in state])

    monkeypatch.setattr(scan, "_store_state", rounded)
    with pltpu.force_tpu_interpret_mode():
        anew = jax.jit(BODIES["kernels"])       # traced with the patch in it
        ours = anew(*inputs, run)
    theirs = scan.plain_scan(*inputs, run)
    gap = float(jnp.abs(ours - theirs).max())
    assert gap > 10 * TOL * float(jnp.abs(theirs).max()), gap


def test_the_rule_between_the_bodies(monkeypatch):
    """``fused_scan_applies``: no on a CPU backend whatever the shapes; on a
    TPU yes at the cell's shapes and no at a width of no whole lane tile, at
    a state of no whole sublane tile and at a row of no whole blocks; and
    where it says no the XLA form runs, where yes the kernels, at the tiles
    ``scan_tiles`` picks."""
    assert jax.default_backend() == "cpu"
    assert not scan.fused_scan_applies(4096, 5120, 16)
    assert scan.fused_scan_positions(4096, 5120, 16) == 0

    def never(*args):
        raise AssertionError("the kernels were called")

    inputs, run, _ = _scan_inputs(SEVERAL, d=64)
    with monkeypatch.context() as patch:
        patch.setattr(scan, "fused_selective_scan", never)
        theirs = scan.selective_scan(*inputs, run)          # the XLA form
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert scan.fused_scan_applies(4096, 5120, 16)
        assert scan.fused_scan_positions(4096, 5120, 16) == 4096
        assert scan.scan_tiles(5120) == (scan.SCAN_BLOCK, scan.SCAN_TILE)
        assert scan.fused_scan_applies(8192, 1024, 8)
        assert not scan.fused_scan_applies(4096, 5120 + 64, 16)
        assert not scan.fused_scan_applies(4096, 5120, 12)
        assert not scan.fused_scan_applies(4096 + 64, 5120, 16)
        assert bool(jnp.array_equal(scan.selective_scan(*inputs, run),
                                    theirs))                # d = 64
    assert bool(jnp.array_equal(
        theirs, scan.chunked_selective_scan(*inputs, run, scan.CHUNK)))
    # told the shapes have tiles, the same call goes through the kernels
    wide, run, _ = _scan_inputs(SEVERAL)
    monkeypatch.setattr(scan, "fused_scan_applies", lambda t, d, n: True)
    monkeypatch.setattr(scan, "scan_tiles", lambda d: (BLOCK, TILE))
    with pltpu.force_tpu_interpret_mode():
        through = jax.jit(lambda *a: scan.selective_scan(*a, run))
        ours = through(*wide)
    np.testing.assert_allclose(
        np.asarray(ours), np.asarray(scan.plain_scan(*wide, run)), rtol=0,
        atol=TOL * float(jnp.abs(ours).max()))
