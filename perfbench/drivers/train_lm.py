"""Driver ``train_lm``: federated language-model jobs through the program's
normal path, ``fedtpu.orchestration.loop.run_experiment``.

``train``'s flow with a language model's data, reference and cost: set-up
(the experiment configuration FIRST, so that a program that has no such
model fails in seconds; then the corpus from ``--seed``, the plain
reference's rounds, one warm-up job that ends where the reference does, the
round program's footprint), then the window's jobs or the traced job, all of
it ``train``'s own code. ``correct``: every job ran its rounds with finite
losses and reported each; every client's loss of the checked rounds and the
global parameters after them are within tolerance of the reference; the same
number of compiles in every job; the run's data name the benchmark's
generator.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from perfbench import datasets_lm, flops_lm, reference_lm
from perfbench.drivers.train import (_overlay, _traced, _window,
                                     experiment_config, job_faults,
                                     params_gap, program_footprint, run_job,
                                     with_run)

# The published keys a configuration file states once, at its top level, and
# the program's ModelConfig takes under the same names.
MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_hidden_layers",
              "num_experts", "num_experts_per_tok", "intermediate_size",
              "vocab_size", "rope_theta", "rms_norm_eps", "norm_topk_prob")
REFERENCE_KEYS = ("num_attention_heads", "num_experts_per_tok", "rope_theta",
                  "rms_norm_eps", "norm_topk_prob")

# The system's first rounds against the plain reference (float32 at 'highest'
# precision, dense experts, whole logits): the largest absolute difference
# over all clients' losses of the checked rounds, and over every global
# parameter after them. The system computes its large matmuls, forward and
# backward, from bfloat16 inputs. Measured on the v5e at the published widths
# over thirteen seeds (my chip runs, PR 25, calls F-G; PERF.md, Findings):
# loss gaps of 5.7e-4 to 1.41e-3 on losses near log(50304) = 10.8, parameter
# gaps of 6.5e-6 to 1.01e-5 on parameters that moved by 1.8e-3 to 2.7e-3 in
# the two rounds. The tolerances are four times the largest gap seen. They
# hold the algorithm (which rows a client holds, the order of its steps, the
# weights of the mean, the server's momentum) and the precision: the
# reference itself, computed with its matmul inputs rounded to float8_e4m3fn
# (the nearest precision below the one the configuration states), lands at
# 3.6e-1 to 5.4e-1 / 1.8e-3 to 2.4e-3 (two seeds), outside both; with
# bfloat16 inputs at 7.1e-4 to 1.2e-3 / 7.0e-6 to 7.3e-6, inside. (At the
# client learning rate first tried, 0.02, two seeds of eleven read 2.2e-2 and
# 5.0e-2 where the others read 2e-3 to 4e-3: the configuration's rate is
# 0.005 for that reason, see its `assumed`.) A configuration's rehearsal
# block states its own for the float32 walk-through on the CPU.
LOSS_TOLERANCE = 5.6e-3
PARAMS_TOLERANCE = 4.0e-5


def model_fields(conf: dict) -> dict:
    return {k: conf[k] for k in MODEL_KEYS}


def reference_rounds(cfg, conf, dataset, rounds: int):
    """``(losses (rounds, C), global params, how far they moved)`` of the
    plain reference from the initial parameters the program draws from
    ``fed.init_seed``."""
    from fedtpu.models.registry import build_model

    init = jax.jit(build_model(cfg.model)[0])(jax.random.key(cfg.fed.init_seed))
    init = jax.tree.map(np.asarray, init)       # the device copy is let go
    rows = [dataset.x_train[dataset.client_of_row == c]
            for c in range(cfg.shard.num_clients)]
    losses, glob = reference_lm.fedavgm_rounds(
        init, rows, rounds, {k: conf[k] for k in REFERENCE_KEYS},
        learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr)
    return losses, glob, params_gap(glob, init)


def run(ctx) -> dict:
    cell, conf, traffic = ctx.cell, ctx.config, ctx.traffic
    if ctx.rehearsal:
        conf = _overlay(conf, conf.get("rehearsal", {}))
        traffic = _overlay(traffic, traffic.get("rehearsal", {}))
    # before any data or reference: a program without this model stops here
    cfg = experiment_config(
        [conf["experiment"], {"model": model_fields(conf)},
         {k: traffic[k] for k in ("run", "fed") if k in traffic},
         {"run": {"mesh_devices": cell["chips"]}}], ctx.seed)
    width = cfg.run.rounds_per_step
    clients = cfg.shard.num_clients
    lines = []

    t = time.perf_counter()
    dataset = datasets_lm.make(conf["dataset"], clients, conf["vocab_size"],
                               ctx.seed)
    counts = datasets_lm.counts(dataset.x_train)
    ctx.clocks["data_build_s"] = time.perf_counter() - t

    k = int(traffic["check_rounds"])
    t = time.perf_counter()
    ref_losses, ref_params, moved = reference_rounds(cfg, conf, dataset, k)
    ctx.clocks["reference_s"] = time.perf_counter() - t
    gc.collect()
    ctx.memory["after_reference"] = ctx.peak_bytes()

    warm_rounds = int(traffic["warmup_rounds"])
    with jax.profiler.TraceAnnotation("warmup"):
        warm, warm_s, _ = run_job(ctx, with_run(cfg, warm_rounds), dataset,
                                  "warmup")
    ctx.clocks["warmup_job_s"] = warm_s
    loss_gap = float(np.max(np.abs(np.stack(warm.loss[:k]) - ref_losses)))
    check = {"rounds": k, "loss_gap": loss_gap,
             "tolerance": conf.get("loss_tolerance", LOSS_TOLERANCE),
             "params_tolerance": conf.get("params_tolerance", PARAMS_TOLERANCE),
             "loss_first_last": [float(np.mean(warm.loss[0])),
                                 float(np.mean(warm.loss[-1]))]}
    same_end = warm_rounds == k         # the job ended where the reference did
    if same_end:
        check["params_gap"] = params_gap(warm.final_params, ref_params)
        check["params_moved"] = moved
    del ref_params
    source_ok = (warm.data.get("generator")
                 == f"perfbench.{conf['dataset']['generator']}")
    faults = job_faults(warm, warm_rounds)
    correct = (loss_gap <= check["tolerance"] and source_ok and faults == 0
               and bool(np.all(np.isfinite(ref_losses)))
               and (not same_end
                    or check["params_gap"] <= check["params_tolerance"]))
    steady = warm.sec_per_round[width:] or warm.sec_per_round
    lines.append({"check": check, "data": warm.data, "source_ok": source_ok,
                  "counts": counts,
                  "warmup": {"rounds": warm_rounds, "seconds": warm_s,
                             "sec_per_round": float(np.median(steady)),
                             "faults": faults}})
    del warm
    gc.collect()
    ctx.memory["after_warmup"] = ctx.peak_bytes()
    t = time.perf_counter()
    ctx.compiles.phase = "setup"
    ctx.memory["round_program"] = program_footprint(cfg, dataset, width)
    ctx.clocks["footprint_s"] = time.perf_counter() - t
    gc.collect()
    ctx.clocks["setup_s"] = time.perf_counter() - ctx.t0
    ctx.compiles.phase = "between"

    cost = flops_lm.round_cost(model_fields(conf), counts, clients)
    ctx.evidence.facts.update(cost=cost, chips=cell["chips"], width=width)
    lines.append({"cost": cost})

    if not ctx.trace:
        out = _window(ctx, cfg, dataset, width, lines)
    else:
        out = _traced(ctx, cfg, dataset, width, traffic, lines)
        # the registry counts over every round of the traced job
        traced = next(l["traced_job"] for l in lines if "traced_job" in l)
        ctx.evidence.facts["lm_positions"] = (traced["rounds"]
                                              * counts["positions"])
    out["correct"] = bool(correct and out["correct"])
    out["lines"] = lines
    return out
