"""Time the Pallas kernels against their XLA equivalents on the real
chip (VERDICT r3 #6), and Mosaic-AOT-compile the RDMA ring's sync path
for a multi-chip v5e topology.

Adopt-on-win policy: a kernel that cannot beat XLA stays a tested
library op and the production path keeps XLA; either way the measured
number is recorded in PERF.md (the round-4 timings are under 'Earlier
records').

Run: ``python benchmarks/pallas_timing.py`` (~2 min on the v5e).
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
from fedtpu.ops.metrics import confusion_matrix
from fedtpu.ops.pallas_kernels import (fused_eval_confusion,
                                       fused_mlp_forward,
                                       weighted_average_clients)
from fedtpu.parallel import make_mesh
from fedtpu.parallel.round import init_federated_state
from fedtpu.utils.timing import force_fetch, marginal_slope
from fedtpu.utils.trees import clone

NUM_CLIENTS = 8




def scan_over(fn_body, const):
    """Scan R applications of fn_body(carry-coupled) so per-call cost is
    slope-measurable; couples the carry so nothing hoists."""
    def gen(R):
        @jax.jit
        def f(c0):
            def body(c, _):
                out = fn_body(c)
                s = sum(jnp.sum(o) for o in jax.tree.leaves(out))
                return jax.tree.map(lambda t: t + 1e-20 * s, c), s
            c, ss = jax.lax.scan(body, c0, length=R)
            return ss[-1]
        return lambda: f(const)
    return gen


def main():
    ds = load_dataset(DataConfig(csv_path=default_income_csv()))
    packed = pack_clients(ds.x_train, ds.y_train,
                          ShardConfig(num_clients=NUM_CLIENTS))
    xd, yd, md = (jnp.asarray(packed.x), jnp.asarray(packed.y),
                  jnp.asarray(packed.mask))
    init_fn, apply_fn = build_model(
        ModelConfig(input_dim=ds.input_dim, num_classes=ds.num_classes))
    tx = build_optimizer(OptimConfig())
    mesh = make_mesh(num_clients=NUM_CLIENTS)
    state = init_federated_state(jax.random.key(0), mesh, NUM_CLIENTS,
                                 init_fn, tx)
    params = clone(state["params"])
    p0 = jax.tree.map(lambda t: t[0], params)   # single-client params
    x_test = jnp.asarray(ds.x_test)
    out = {}

    # ---- 1. fused_mlp_forward vs XLA apply (the held-out eval shape)
    m_pal = marginal_slope(scan_over(
        lambda p: fused_mlp_forward(p, x_test), p0))
    m_xla = marginal_slope(scan_over(
        lambda p: apply_fn(p, x_test), p0))
    out["heldout_eval_forward"] = {"pallas_s": m_pal, "xla_s": m_xla}

    # ---- 2. weighted_average_clients vs the XLA weighted mean, on the
    # flat per-leaf stacks the aggregation actually reduces
    w = md.sum(axis=1).astype(jnp.float32)
    flat = jnp.concatenate(
        [l.reshape(NUM_CLIENTS, -1) for l in jax.tree.leaves(params)],
        axis=1)

    def xla_wavg(f):
        return (w @ f) / w.sum()

    m_pal_w = marginal_slope(scan_over(
        lambda f: weighted_average_clients(f, w), flat))
    m_xla_w = marginal_slope(scan_over(xla_wavg, flat))
    out["weighted_average"] = {"pallas_s": m_pal_w, "xla_s": m_xla_w,
                               "flat_dim": int(flat.shape[1])}

    # ---- 3. fused eval->confusion vs the XLA eval chain (in-round shape)
    m_pal_e = marginal_slope(scan_over(
        lambda p: fused_eval_confusion(p, xd, yd, md, ds.num_classes),
        params))
    # The XLA chain is fast enough (~2-5 us/iter) that the default
    # windows sink under dispatch jitter; widen them.
    m_xla_e = marginal_slope(scan_over(
        lambda p: jax.vmap(lambda pp, xx, yy, mm: confusion_matrix(
            yy, jnp.argmax(apply_fn(pp, xx), -1), mm,
            ds.num_classes))(p, xd, yd, md), params),
        lens=(2000, 10000), reps=6)
    out["eval_confusion"] = {"pallas_s": m_pal_e, "xla_s": m_xla_e}

    # ---- 4. Mosaic AOT compile of the ring sync path for 4 v5e chips
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from fedtpu.parallel.ring_pallas import pallas_ring_all_reduce_sum

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2x1")
    devs = np.asarray(topo.devices)[:4]
    ring_mesh = Mesh(devs.reshape(4), ("clients",))

    def ring_fn(t):
        return jax.shard_map(
            lambda u: pallas_ring_all_reduce_sum(u[0], "clients", 4,
                                                 interpret=False)[None],
            mesh=ring_mesh, in_specs=P("clients"),
            out_specs=P("clients"))(t)

    sharded = jax.ShapeDtypeStruct(
        (4, 1024), jnp.float32,
        sharding=NamedSharding(ring_mesh, P("clients")))
    try:
        jax.jit(ring_fn).lower(sharded).compile()
        out["ring_sync_aot_v5e_2x2"] = True
    except Exception as e:
        out["ring_sync_aot_v5e_2x2"] = False
        out["ring_sync_aot_error"] = f"{type(e).__name__}: {e}"[:500]

    print(json.dumps(out, indent=2, default=float))
    for name, row in out.items():
        if isinstance(row, dict) and "pallas_s" in row:
            r = row["xla_s"] / row["pallas_s"]
            verdict = ("pallas wins" if r > 1.15
                       else "xla wins" if r < 0.87 else "tie")
            print(f"[pallas] {name}: pallas {row['pallas_s']*1e6:.2f} us vs "
                  f"xla {row['xla_s']*1e6:.2f} us -> {verdict}")
    print(f"[pallas] ring sync path AOT Mosaic compile for v5e 2x2: "
          f"{'ok' if out['ring_sync_aot_v5e_2x2'] else 'FAILED'}")


if __name__ == "__main__":
    main()
