"""Roofline + attribution for the income round program (VERDICT r3 #1).

Answers, with measurements on the real chip, WHY the headline round's
marginal MFU sits near 22% and what bound it actually saturates:

1. XLA cost/memory analysis of the compiled round: FLOPs, bytes
   accessed, and XLA's own ``optimal_seconds`` roofline estimate.
2. Marginal sec/round of the round and of its stages (train-only,
   train+aggregation, full) via the scan-length SLOPE method — two scan
   depths far apart, (t2 - t1) / (R2 - R1), which cancels the fixed
   dispatch+fetch cost exactly (fedtpu.utils.timing methodology).
3. Measured streaming ceilings for the round's activation-sized tensors
   (f32 and bf16 elementwise passes over the exact shapes).
4. MFU of the SAME round program at MXU-sized shapes (hidden 512/1024),
   demonstrating the framework clears 40% MFU whenever the workload's
   arithmetic intensity allows it.

Conclusion this script reproduces (PERF.md 'Earlier records'):
the income round is BYTE-throughput bound on its (8, 1000, {50,200})
activation streams, which XLA already moves as bf16/u8; its 22%
marginal MFU is that bandwidth roofline, not scheduling headroom — the
program beats XLA's own HBM-model estimate ~3x via VMEM residency and
runs within ~1.2x of the measured elementwise streaming time of its
tensors, while the identical round at hidden 512 reaches >50% MFU.

Run: ``python benchmarks/roofline.py`` (~3 min on the v5e).
"""

from __future__ import annotations

import json
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import jax
import jax.numpy as jnp
import numpy as np

from fedtpu.config import (DataConfig, ModelConfig, OptimConfig, ShardConfig,
                           default_income_csv)
from fedtpu.data import load_dataset
from fedtpu.data.sharding import pack_clients
from fedtpu.models import build_model
from fedtpu.ops import build_optimizer
from fedtpu.parallel import client_sharding, make_mesh
from fedtpu.parallel.round import build_round_fn, init_federated_state
from fedtpu.training.client import make_local_train_step
from fedtpu.utils.timing import (compile_with_flops, force_fetch,
                                 marginal_slope, measured_peak_flops)
from fedtpu.utils.trees import clone

NUM_CLIENTS = 8




def income_setup():
    ds = load_dataset(DataConfig(csv_path=default_income_csv()))
    mesh = make_mesh(num_clients=NUM_CLIENTS)
    shard = client_sharding(mesh)
    packed = pack_clients(ds.x_train, ds.y_train,
                          ShardConfig(num_clients=NUM_CLIENTS))
    batch = {"x": jax.device_put(packed.x, shard),
             "y": jax.device_put(packed.y, shard),
             "mask": jax.device_put(packed.mask, shard)}
    init_fn, apply_fn = build_model(
        ModelConfig(input_dim=ds.input_dim, num_classes=ds.num_classes))
    tx = build_optimizer(OptimConfig())
    state = init_federated_state(jax.random.key(0), mesh, NUM_CLIENTS,
                                 init_fn, tx)
    return ds, mesh, shard, packed, batch, init_fn, apply_fn, tx, state


def main():
    (ds, mesh, shard, packed, batch,
     init_fn, apply_fn, tx, state) = income_setup()
    dev = mesh.devices.ravel()[0]
    peak = measured_peak_flops(device=dev)
    out = {"peak_flops": peak, "backend": dev.platform}

    # ---- 1. compiled-program analysis
    step1 = build_round_fn(mesh, apply_fn, tx, ds.num_classes,
                           rounds_per_step=1)
    compiled = step1.lower(clone(state), batch).compile()
    ca = compiled.cost_analysis()
    flops = float(ca["flops"])
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    optimal_s = float(ca.get("optimal_seconds", 0.0))
    out["flops_per_round"] = flops
    out["bytes_accessed"] = bytes_accessed
    out["xla_optimal_seconds"] = optimal_s

    # ---- 2. marginal attribution
    def full(R):
        step = build_round_fn(mesh, apply_fn, tx, ds.num_classes,
                              rounds_per_step=R)
        return lambda: step(clone(state), batch)[1]["client_mean"]["accuracy"]

    local_train = make_local_train_step(apply_fn, tx)
    xd, yd, md = (jnp.asarray(packed.x), jnp.asarray(packed.y),
                  jnp.asarray(packed.mask))

    def train_only(R):
        @jax.jit
        def f(params, opt_state):
            def body(c, _):
                p, o = c
                p2, o2, loss = jax.vmap(local_train)(p, o, xd, yd, md)
                return (p2, o2), loss
            (p, o), losses = jax.lax.scan(body, (params, opt_state),
                                          length=R)
            return losses[-1].sum() + jax.tree.leaves(p)[0].sum()
        p0, o0 = clone(state["params"]), clone(state["opt_state"])
        return lambda: f(p0, o0)

    def train_agg(R):
        w = md.sum(axis=1)

        @jax.jit
        def f(params, opt_state):
            def body(c, _):
                p, o = c
                p2, o2, loss = jax.vmap(local_train)(p, o, xd, yd, md)
                g = jax.tree.map(
                    lambda t: (w.reshape((NUM_CLIENTS,) + (1,) * (t.ndim - 1))
                               * t).sum(0) / w.sum(), p2)
                p3 = jax.tree.map(
                    lambda gl, t: jnp.broadcast_to(gl[None], t.shape), g, p2)
                return (p3, o2), loss
            (p, o), losses = jax.lax.scan(body, (params, opt_state),
                                          length=R)
            return losses[-1].sum() + jax.tree.leaves(p)[0].sum()
        p0, o0 = clone(state["params"]), clone(state["opt_state"])
        return lambda: f(p0, o0)

    # Stage slopes carry ~1-2 us of window jitter each (the differences
    # below inherit it doubled); more reps narrow the min-window noise.
    m_full = marginal_slope(full, reps=6)
    m_train = marginal_slope(train_only, reps=6)
    m_agg = marginal_slope(train_agg, reps=6)
    out["marginal_s"] = {"full_round": m_full, "train_only": m_train,
                         "train_plus_agg": m_agg,
                         "eval_metrics": m_full - m_agg,
                         "aggregation": m_agg - m_train}
    out["marginal_mfu"] = flops / (m_full * peak)
    out["flops_floor_s"] = flops / peak

    # ---- 3. streaming ceilings on the round's activation shapes
    ceilings = {}
    for dt, name in ((jnp.float32, "f32"), (jnp.bfloat16, "bf16")):
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((8, 1000, 200)), dt)

        def gen(R, x=x, dt=dt):
            @jax.jit
            def f(x0):
                def body(c, _):
                    return (c * jnp.asarray(0.9999, dt)
                            + jnp.asarray(1e-4, dt),
                            c.astype(jnp.float32).sum())
                c, ss = jax.lax.scan(body, x0, length=R)
                return ss[-1]
            return lambda: f(x)
        m = marginal_slope(gen)
        nbytes = 2 * x.dtype.itemsize * x.size
        ceilings[name] = {"s_per_pass": m, "tb_per_s": nbytes / m / 1e12}
    out["stream_ceiling_8x1000x200"] = ceilings

    # ---- 4. same round program at MXU-sized shapes
    shapes = []
    for rows, hidden, lens in ((1000, (512, 512), (200, 800)),
                               (8000, (512, 512), (50, 200))):
        ds2 = load_dataset(DataConfig(csv_path=None,
                                      synthetic_rows=rows * NUM_CLIENTS,
                                      synthetic_features=14))
        packed2 = pack_clients(ds2.x_train, ds2.y_train,
                               ShardConfig(num_clients=NUM_CLIENTS))
        batch2 = {"x": jax.device_put(packed2.x, shard),
                  "y": jax.device_put(packed2.y, shard),
                  "mask": jax.device_put(packed2.mask, shard)}
        init2, apply2 = build_model(
            ModelConfig(input_dim=ds2.input_dim, hidden_sizes=hidden,
                        num_classes=ds2.num_classes))
        state2 = init_federated_state(jax.random.key(0), mesh, NUM_CLIENTS,
                                      init2, tx)

        def gen(R, apply2=apply2, state2=state2, batch2=batch2, ds2=ds2):
            step = build_round_fn(mesh, apply2, tx, ds2.num_classes,
                                  rounds_per_step=R)
            return lambda: step(clone(state2),
                                batch2)[1]["client_mean"]["accuracy"]
        s1 = build_round_fn(mesh, apply2, tx, ds2.num_classes,
                            rounds_per_step=1)
        _, fl2 = compile_with_flops(s1, clone(state2), batch2)
        m2 = marginal_slope(gen, lens)
        shapes.append({"rows_per_client": int(packed2.x.shape[1]),
                       "hidden": list(hidden), "marginal_s": m2,
                       "flops": fl2, "mfu": fl2 / (m2 * peak)})
    out["mxu_sized_rounds"] = shapes

    print(json.dumps(out, indent=2, default=float))
    head = out["marginal_s"]
    print(f"\n[roofline] income round marginal {m_full*1e6:.1f} us "
          f"(train {head['train_only']*1e6:.1f}, eval+metrics "
          f"{head['eval_metrics']*1e6:.1f}, agg "
          f"{head['aggregation']*1e6:.1f}); flops floor "
          f"{out['flops_floor_s']*1e6:.1f} us -> marginal MFU "
          f"{100*out['marginal_mfu']:.1f}%")
    print(f"[roofline] XLA bytes accessed {bytes_accessed/1e6:.1f} MB/round; "
          f"XLA HBM-model optimal {optimal_s*1e6:.1f} us "
          f"(we run {optimal_s/m_full:.1f}x faster: VMEM residency + bf16 "
          "streams)")
    for s in shapes:
        print(f"[roofline] hidden {s['hidden']} rows/client "
              f"{s['rows_per_client']}: {100*s['mfu']:.1f}% MFU — the same "
              "round program clears 40% when shapes are MXU-sized")


if __name__ == "__main__":
    main()
