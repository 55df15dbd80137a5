"""Per-feature overhead of the aggregation modes, measured on the income
MLP at 8 clients (the headline bench.py shape): sec/round at
rounds_per_step=100 for each mode vs the plain weighted mean.

Every mode runs inside the same compiled multi-round scan, so this is the
true marginal cost of the richer aggregation math (server optimizers, DP
clip+noise, int8 quantize/gather, coordinate-wise order statistics) on the
hot path. Prints one JSON line per mode.

Usage: python benchmarks/feature_overhead.py [--reps 30] [--rounds-per-step 100]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from fedtpu.config import DataConfig, ModelConfig, OptimConfig, ShardConfig, \
    default_income_csv
from fedtpu.data.sharding import pack_clients
from fedtpu.data.tabular import load_tabular_dataset
from fedtpu.models import build_model
from fedtpu.ops import build_optimizer
from fedtpu.ops.server_opt import make_server_optimizer
from fedtpu.parallel import make_mesh, client_sharding
from fedtpu.parallel.round import build_round_fn, init_federated_state

NUM_CLIENTS = 8

MODES = {
    "mean": {},
    "local_steps_5": dict(local_steps=5),
    "fedadam": dict(server_opt="fedadam"),
    "dp": dict(dp_clip_norm=1.0, dp_noise_multiplier=0.1,
               weighting="uniform"),
    "int8": dict(compress="int8"),
    "median": dict(robust_aggregation="median", weighting="uniform"),
    "trimmed_mean": dict(robust_aggregation="trimmed_mean",
                         weighting="uniform"),
    "byzantine_2": dict(byzantine_clients=2),
}


def bench_mode(name: str, kw: dict, ds, reps: int, rps: int,
               peak_flops: float) -> dict:
    kw = dict(kw)
    mesh = make_mesh(num_clients=NUM_CLIENTS)
    shard = client_sharding(mesh)
    packed = pack_clients(ds.x_train, ds.y_train,
                          ShardConfig(num_clients=NUM_CLIENTS))
    batch = {k: jax.device_put(v, shard) for k, v in
             {"x": packed.x, "y": packed.y, "mask": packed.mask}.items()}
    init_fn, apply_fn = build_model(ModelConfig(input_dim=ds.input_dim,
                                                num_classes=ds.num_classes))
    tx = build_optimizer(OptimConfig())

    server = None
    if "server_opt" in kw:
        server = make_server_optimizer(kw.pop("server_opt"),
                                       learning_rate=0.02)
    state_server = server
    if state_server is None and kw.get("dp_clip_norm", 0) > 0:
        from fedtpu.ops.server_opt import identity_server_optimizer
        state_server = identity_server_optimizer()
    state = init_federated_state(
        jax.random.key(0), mesh, NUM_CLIENTS, init_fn, tx,
        server_opt=state_server,
        shared_start=kw.get("compress", "none") != "none")
    step = build_round_fn(mesh, apply_fn, tx, ds.num_classes,
                          rounds_per_step=rps, server_opt=server, **kw)

    # Fetch-forced timing + flops floor — see fedtpu.utils.timing docstring
    # for the methodology. SEVERAL independent samples per mode (each
    # itself min-of-3 windows): the dispatch share jitters with host load,
    # and a single sample let added work appear cheaper than the baseline
    # (review r2 weak #5) — the caller compares BANDS, not points.
    from fedtpu.utils.timing import compile_with_flops, timed_rounds

    step, flops_per_round = compile_with_flops(step, state, batch)
    samples = []
    for _ in range(5):
        sec, state, m = timed_rounds(step, state, batch, reps, rps,
                                     peak_flops, flops_per_round, label=name)
        samples.append(sec)
    samples.sort()
    return {"mode": name,
            "sec_per_round": float(f"{samples[len(samples) // 2]:.4g}"),
            "sec_per_round_range": [float(f"{samples[0]:.4g}"),
                                    float(f"{samples[-1]:.4g}")],
            "rounds_per_step": rps,
            "backend": mesh.devices.ravel()[0].platform}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--rounds-per-step", type=int, default=100)
    args = ap.parse_args()

    from fedtpu.utils.timing import measured_peak_flops

    peak = measured_peak_flops(dtype="float32")
    ds = load_tabular_dataset(DataConfig(csv_path=default_income_csv()))
    base = None
    for name, kw in MODES.items():
        row = bench_mode(name, kw, ds, args.reps, args.rounds_per_step, peak)
        if name == "mean":
            base = row
        lo, hi = row["sec_per_round_range"]
        blo, bhi = base["sec_per_round_range"]
        row["vs_mean"] = float(
            f"{row['sec_per_round'] / base['sec_per_round']:.3g}")
        # Ratio band from the two sample bands; a row only claims a real
        # overhead (or saving) when the bands do NOT overlap. Overlapping
        # bands => the difference is within dispatch noise, and the row
        # says so instead of printing a meaningless sub-1.0 ratio.
        row["vs_mean_range"] = [float(f"{lo / bhi:.3g}"),
                                float(f"{hi / blo:.3g}")]
        row["significant"] = bool(lo > bhi or hi < blo)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
