"""Why the 2-D engine exists: per-device memory scaling (VERDICT r3 #3).

The reference replicates every model whole — one full copy per MPI rank
(FL_CustomMLPCLassifierImplementation_Multiple_Rounds.py:42) — so its
largest trainable model is whatever one process's memory holds. fedtpu's
1-D engine inherits that per-device shape: each client slot carries full
params + full Adam moments. The 2-D ('clients','model') engine
(fedtpu.parallel.tp) shards the hidden weights over the model axis; this
script produces the NUMBERS that justify it:

1. MEASURED per-device live state bytes on the virtual 8-device mesh for
   a fixed 2-client federation as tp grows 1 -> 2 -> 4 (1-D engine = the
   tp=1 baseline, on 2 devices). Bytes come from the actual device
   buffers (``addressable_shards``), not a model: params + Adam moments
   per device drop ~1/tp, and the tp=4 round genuinely executes at a
   size where the 1-D engine needs >4x the per-device state.
2. XLA compiled-program memory analysis (argument/output/temp/peak) of
   each round program — the compiler's own per-device accounting,
   including scratch.
3. EXACT accounting (jax.eval_shape — no allocation) of both layouts at
   v5e scale: the hidden=[32k,32k,32k] MLP whose per-device
   params+moments (24.4 GiB) cannot fit a 16-GiB v5e chip under the 1-D
   engine, while tp=2 (12.2 GiB) fits and tp=4 (6.1 GiB) fits with room
   for activations. Same math the ARCHITECTURE doc quotes.

The scaling law being demonstrated: per-device state bytes ~=
(C/dp) * (P_sharded/tp + P_replicated) * 12 B, where 12 B = fp32 param
+ Adam m + v. Only the logits head and the row-Linear biases are
replicated over 'model' (fedtpu/parallel/tp.py:mlp_tp_specs), so
P_replicated is tiny for wide MLPs and the drop tracks 1/tp closely.

Run: ``python benchmarks/tp_memory.py`` (~1 min, CPU — forces the
virtual 8-device mesh; tp>1 needs more devices than the 1-chip box).
"""

from __future__ import annotations

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, jax.devices()

import jax.numpy as jnp
import numpy as np

from fedtpu.config import ModelConfig, OptimConfig, ShardConfig
from fedtpu.data.sharding import pack_clients
from fedtpu.data.tabular import synthetic_income_like
from fedtpu.models import build_model
from fedtpu.ops import build_optimizer
from fedtpu.parallel import client_sharding, make_mesh, tp
from fedtpu.parallel.round import build_round_fn, init_federated_state
from fedtpu.utils.trees import max_device_bytes

NUM_CLIENTS = 2          # fixed federation; chips-per-client is the axis
V5E_HBM_GIB = 16.0       # v5e: 16 GiB HBM per chip
GIB = 1024.0 ** 3


def state_bytes(state) -> int:
    """Max-over-devices of measured params+opt_state bytes (the round
    counter and any server state ride along; they are scalars here)."""
    return max_device_bytes({"params": state["params"],
                             "opt": state["opt_state"]})


# ---------------------------------------------------------------- measured
def measured_scaling(hidden=(8192, 8192), input_dim=1024, rows=256):
    """Build the same 2-client federation on the 1-D engine and on the 2-D
    engine at tp in {2, 4}; measure per-device state bytes and the
    compiler's memory stats; run one real round on each."""
    x, y = synthetic_income_like(rows, input_dim, 2, seed=0)
    packed = pack_clients(x, y, ShardConfig(num_clients=NUM_CLIENTS,
                                            shuffle=False))
    init_fn, apply_fn = build_model(ModelConfig(input_dim=input_dim,
                                                hidden_sizes=hidden))
    tx = build_optimizer(OptimConfig())
    key = jax.random.key(0)
    batch_np = {"x": packed.x, "y": packed.y, "mask": packed.mask}
    rows_out = []

    def run(label, mesh, state, step, batch):
        compiled = step.lower(state, batch).compile()
        ma = compiled.memory_analysis()
        # Execute through the AOT executable (a jit call would compile the
        # same program a second time — the AOT compile shares no cache).
        state2, metrics = compiled(state, batch)   # really execute one round
        jax.block_until_ready(state2["params"])
        rows_out.append({
            "engine": label,
            "devices": int(np.prod(mesh.devices.shape)),
            "state_bytes_per_device": state_bytes(state2),
            "xla_argument_bytes": int(ma.argument_size_in_bytes),
            "xla_temp_bytes": int(ma.temp_size_in_bytes),
            "xla_peak_bytes": int(ma.peak_memory_in_bytes),
        })
        return state2

    # 1-D engine: 2 devices, one client's FULL model each — the reference's
    # replication shape (FL_CustomMLP...:42) on fedtpu's fast path.
    mesh1 = make_mesh(num_devices=NUM_CLIENTS, num_clients=NUM_CLIENTS)
    s1 = init_federated_state(key, mesh1, NUM_CLIENTS, init_fn, tx)
    b1 = {k: jax.device_put(v, client_sharding(mesh1))
          for k, v in batch_np.items()}
    run("1d", mesh1,  s1,
        build_round_fn(mesh1, apply_fn, tx, 2), b1)

    for mp in (2, 4):
        mesh2 = tp.make_mesh_2d(mp, NUM_CLIENTS)
        s2 = tp.init_federated_state_2d(key, mesh2, NUM_CLIENTS, init_fn, tx)
        b2 = {k: jax.device_put(v, tp.batch_sharding_2d(mesh2))
              for k, v in batch_np.items()}
        run(f"2d tp={mp}", mesh2, s2,
            tp.build_round_fn_2d(mesh2, apply_fn, tx, 2), b2)
    return rows_out


# ------------------------------------------------------- exact accounting
def exact_per_device_bytes(input_dim, hidden, num_classes, mp, dp=1,
                           clients_per_slot=1):
    """Per-device params+opt bytes for the 2-D layout, via eval_shape (no
    allocation): each leaf's bytes divided by the product of mesh-axis
    extents its PartitionSpec names. mp=1 == the 1-D engine's layout."""
    init_fn, _ = build_model(ModelConfig(input_dim=input_dim,
                                         hidden_sizes=hidden,
                                         num_classes=num_classes))
    tx = build_optimizer(OptimConfig())
    keys = jax.ShapeDtypeStruct((dp * clients_per_slot, 2), jnp.uint32)
    params = jax.eval_shape(jax.vmap(lambda k: init_fn(
        jax.random.wrap_key_data(k))), keys)
    opt = jax.eval_shape(jax.vmap(tx.init), params)
    specs = tp.tp_specs(params)
    extent = {"clients": dp, "model": mp}

    def leaf_bytes(leaf, spec):
        denom = 1
        for axis in spec:
            if axis is not None:
                denom *= extent[axis]
        return int(np.prod(leaf.shape)) * leaf.dtype.itemsize / denom

    pb = sum(jax.tree.leaves(jax.tree.map(leaf_bytes, params, specs)))
    # Adam: m and v mirror the param layout (sharding propagation); counts
    # are scalars. Charge every non-scalar opt leaf at the param ratio.
    ob = 2 * pb
    scalars = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                  for l in jax.tree.leaves(opt) if l.ndim <= 1)
    return pb + ob + scalars


def v5e_table(input_dim=1024, hidden=(32768, 32768, 32768), num_classes=16):
    rows = []
    for mp in (1, 2, 4, 8):
        b = exact_per_device_bytes(input_dim, hidden, num_classes, mp)
        rows.append({"tp": mp, "per_device_gib": b / GIB,
                     "fits_v5e": b / GIB < V5E_HBM_GIB})
    return rows


def main():
    print(f"== measured on the virtual 8-device mesh "
          f"(C={NUM_CLIENTS} clients, hidden=[8192,8192] fp32) ==")
    meas = measured_scaling()
    base = meas[0]["state_bytes_per_device"]
    for r in meas:
        r["vs_1d"] = round(base / r["state_bytes_per_device"], 2)
        print(json.dumps(r))
    # The guarantees docs/ARCHITECTURE.md quotes: tp=2 halves, tp=4 quarters
    # (within 10% — the replicated logits head and row-biases are the slack).
    assert meas[1]["vs_1d"] > 1.8 and meas[2]["vs_1d"] > 3.6, meas
    assert meas[2]["xla_peak_bytes"] < meas[0]["xla_peak_bytes"] / 2, meas

    print(f"\n== exact accounting at v5e scale (hidden=[32768]*3, fp32, "
          f"Adam; {V5E_HBM_GIB:.0f} GiB HBM/chip) ==")
    tab = v5e_table()
    for r in tab:
        print(json.dumps(r))
    assert not tab[0]["fits_v5e"] and tab[1]["fits_v5e"], tab
    print("\n1-D engine (full replication, the reference's layout) cannot "
          "fit this model on a v5e chip; tp=2 fits, tp=4 leaves >9 GiB "
          "for activations.")


if __name__ == "__main__":
    main()
