"""Pallas TPU kernels for the fedtpu hot ops.

The reference has no custom kernels anywhere (its only accelerator touchpoint
is torch's prebuilt CUDA dispatch, FL_CustomMLP...:33 — SURVEY.md §2); these
are fedtpu's TPU-native equivalents for the two per-round hot paths:

* ``fused_mlp_forward`` — the whole Linear->ReLU->...->Linear stack in ONE
  kernel: the input tile is DMA'd to VMEM once, every layer's matmul runs on
  the MXU with activations staying resident in VMEM, and only the logits go
  back to HBM. XLA already fuses the elementwise ReLU/bias into the matmuls;
  what it does not do is keep the inter-layer activations out of HBM for the
  whole stack — for the income MLP (14->50->200->2) that halves HBM traffic.
* ``weighted_average_clients`` — the FedAvg reduction over a device's local
  client block as a single (1,C)@(C,D) MXU contraction in VMEM (the in-kernel
  analogue of the rank-0 weighted average, FL_CustomMLP...:108-116).

All kernels run in interpret mode on CPU, which is how the unit tests check
bit-parity against the pure-XLA implementations; compiled, they are held to
the v5e's own compiler in tests/test_aot_tpu_compile.py and the fused
forward runs on the chip in chip_smoke.py's ``--use-pallas`` eval. ``fused_mlp_forward`` grids
the row axis to stay within the VMEM budget; ``fused_eval_confusion`` holds
one client's rows at a time and refuses shapes whose activations would not
fit (its confusion contraction needs the whole shard in one pass).

Measured on the v5e in round 4 (PERF.md 'Earlier records'; not re-timed
since): XLA beats every kernel here at the income shapes — Mosaic's matmul codegen
for pad-dominated operands (K=14 / N=2 against the 128-lane MXU) is several
times slower than XLA's, the same effect that sank the whole-round
mega-kernel attempt (benchmarks/mega_kernel_attempt.py). The kernels remain
as tested library ops and educational artifacts; every production path keeps
XLA by measurement, not by default.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Per-kernel VMEM budget guard (per core ~16 MB; leave headroom for weights,
# double buffering, and the output tile).
_VMEM_BUDGET_BYTES = 8 * 1024 * 1024


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _row_tile(n_rows: int, widest: int) -> int:
    """Pick a row-tile size: multiple of 8 (f32 sublane), capped so the
    widest activation tile stays within the VMEM budget."""
    cap = max(8, _VMEM_BUDGET_BYTES // max(1, widest * 4))
    cap = (cap // 8) * 8
    tile = min(512, cap)
    while n_rows % tile:
        tile -= 8
        if tile <= 8:
            return 8
    return tile


def _mlp_kernel(num_layers: int, *refs):
    x_ref = refs[0]
    out_ref = refs[-1]
    h = x_ref[:]
    for i in range(num_layers):
        w = refs[1 + 2 * i][:]
        b = refs[2 + 2 * i][:]
        h = jnp.dot(h, w, preferred_element_type=jnp.float32) + b
        if i < num_layers - 1:
            h = jnp.maximum(h, 0.0)
    out_ref[:] = h


def fused_mlp_forward(params, x: jax.Array,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Pallas drop-in for ``fedtpu.models.mlp.mlp_apply`` (float32 path).

    Any (N, D) input: N is zero-padded up to a row-tile multiple internally
    and the padding rows are sliced off the output, so callers outside the
    padded pipeline (e.g. raw test splits) are safe. Row-gridded when the
    batch is too tall for one VMEM tile.
    """
    if interpret is None:
        interpret = _auto_interpret()
    layers = params["layers"]
    num_layers = len(layers)
    n_orig, d_in = x.shape
    n = -(-n_orig // 8) * 8
    if n != n_orig:
        x = jnp.pad(x, ((0, n - n_orig), (0, 0)))
    dims = [d_in] + [l["w"].shape[1] for l in layers]
    widest = max(dims)
    tile = _row_tile(n, widest)
    grid = (n // tile,)

    weight_args = []
    in_specs = [pl.BlockSpec((tile, d_in), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)]
    for l in layers:
        w, b = l["w"], l["b"]
        weight_args.extend([w.astype(jnp.float32),
                            b.astype(jnp.float32).reshape(1, -1)])
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        in_specs.append(pl.BlockSpec((1, b.shape[0]), lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))

    out_dim = dims[-1]
    # Inside shard_map (check_vma=True) the output's varying-manual-axes must
    # be declared explicitly; propagate the input's.
    out = pl.pallas_call(
        functools.partial(_mlp_kernel, num_layers),
        out_shape=jax.ShapeDtypeStruct((n, out_dim), jnp.float32,
                                       vma=jax.typeof(x).vma),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile, out_dim), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(x.astype(jnp.float32), *weight_args)
    return out[:n_orig] if n != n_orig else out


def _eval_conf_kernel(num_layers, num_classes, n_rows, x_ref, y_ref,
                      *refs):
    """Per-client fused eval: forward -> argmax -> masked confusion, all
    VMEM-resident; only the (K, K) counts (padded to a tile) leave."""
    out_ref = refs[-1]
    c = pl.program_id(0)
    h = x_ref[0]
    for i in range(num_layers):
        w = refs[2 * i][0]
        b = refs[2 * i + 1][pl.ds(c, 1), :]
        h = jnp.dot(h, w, preferred_element_type=jnp.float32) + b
        if i < num_layers - 1:
            h = jnp.maximum(h, 0.0)
    # First-max argmax via 2-D column scans (Mosaic rejects 1-D layouts
    # with row offsets, so everything stays (N, 1)-shaped).
    best = h[:, 0:1]
    idx = jnp.zeros((n_rows, 1), jnp.float32)
    for k in range(1, num_classes):
        cur = h[:, k:k + 1]
        idx = jnp.where(cur > best, jnp.float32(k), idx)
        best = jnp.maximum(best, cur)
    pred_oh = jnp.concatenate(
        [(idx == jnp.float32(k)).astype(jnp.float32)
         for k in range(num_classes)], axis=1)
    oh = y_ref[0]                       # pre-masked one-hot labels (N, K)
    conf = jax.lax.dot_general(oh, pred_oh, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST)
    out_ref[0] = jnp.pad(conf, ((0, 8 - num_classes),
                                (0, 128 - num_classes)))


def fused_eval_confusion(params, x: jax.Array, y: jax.Array,
                         mask: jax.Array, num_classes: int,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Batched-over-clients fused eval: ``(C, K, K)`` confusion matrices
    from client-stacked params ``{layers: [{w: (C,di,dj), b: (C,dj)}]}``
    and data ``x (C,N,D), y (C,N), mask (C,N)`` in ONE kernel — the
    in-VMEM analogue of ``vmap(local_eval)`` (fedtpu.training.client).
    Bit-parity with the XLA chain is pinned in tests/test_pallas.py;
    measured on the v5e it LOSES to the XLA chain by a wide margin
    (PERF.md 'Earlier records': Mosaic's matmul codegen at these
    pad-dominated shapes), so every production path
    keeps XLA and this kernel stays a library/educational op.
    ``num_classes`` must be <= 8 (the padded output tile's sublane
    count)."""
    if interpret is None:
        interpret = _auto_interpret()
    if num_classes > 8:
        raise ValueError(f"num_classes={num_classes} > 8 unsupported "
                         "(confusion tile padding)")
    layers = params["layers"]
    nl = len(layers)
    c, n, d = x.shape
    # No row tiling here — the confusion contraction consumes the whole
    # shard in one pass — so the widest per-client activation must fit
    # the VMEM budget; refuse loudly instead of failing in Mosaic.
    widest = max([d, num_classes] + [l["w"].shape[-1] for l in layers])
    if n * widest * 4 > _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"fused_eval_confusion: {n} rows x {widest} widest dim "
            f"exceeds the {_VMEM_BUDGET_BYTES >> 20} MB VMEM budget; "
            "use the XLA eval path for shards this large")
    # Mask folded into the labels' one-hot once, outside the kernel.
    ohm = (jax.nn.one_hot(y, num_classes, dtype=jnp.float32)
           * mask.astype(jnp.float32)[..., None])
    in_specs = [
        pl.BlockSpec((1, n, d), lambda c: (c, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, n, num_classes), lambda c: (c, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [x.astype(jnp.float32), ohm]
    for l in layers:
        w, b = l["w"], l["b"]
        in_specs.append(pl.BlockSpec((1,) + w.shape[1:],
                                     lambda c: (c, 0, 0),
                                     memory_space=pltpu.VMEM))
        in_specs.append(pl.BlockSpec(b.shape, lambda c: (0, 0),
                                     memory_space=pltpu.VMEM))
        args.extend([w.astype(jnp.float32), b.astype(jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_eval_conf_kernel, nl, num_classes, n),
        out_shape=jax.ShapeDtypeStruct((c, 8, 128), jnp.float32),
        grid=(c,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 8, 128), lambda c: (c, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(*args)
    return out[:, :num_classes, :num_classes]


def _wavg_kernel(x_ref, w_ref, out_ref):
    # (1, C) @ (C, D) on the MXU: the whole weighted average in one pass.
    # HIGHEST precision: the MXU's default bf16 multiply costs ~1e-3 relative
    # error, unacceptable for parameter averaging.
    out_ref[:] = jnp.dot(w_ref[:], x_ref[:],
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)


def weighted_average_clients(stacked: jax.Array, weights: jax.Array,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Weighted average over the leading clients axis of ``stacked`` (C, D):
    ``sum_c weights[c] * stacked[c] / sum_c weights[c]`` — the FedAvg
    aggregation (FL_CustomMLP...:112-115) as one VMEM-resident contraction."""
    if interpret is None:
        interpret = _auto_interpret()
    c, d = stacked.shape
    total = jnp.maximum(weights.sum(), 1e-30)
    wn = (weights / total).reshape(1, c).astype(jnp.float32)
    out = pl.pallas_call(
        _wavg_kernel,
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        in_specs=[pl.BlockSpec((c, d), memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, c), memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, d), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(stacked.astype(jnp.float32), wn)
    return out[0]
