"""Driver ``train_xing4``: federated jobs of the Xing4.0 stack (latent
attention on a four-stream residual, a leading dense layer, held experts, a
multi-token-prediction module) through the program's normal path,
``fedtpu.orchestration.loop.run_experiment``.

``train_nemotron_h``'s flow with this model's configuration keys, reference
and cost: set-up (the experiment configuration FIRST, so that a program that
has no such model fails in seconds; then the corpus from ``--seed`` over the
vocabulary slice, the round program compiling on a thread of its own while
the plain reference runs its rounds, one warm-up job that ends where the
reference does), then the window's jobs or the traced job, all of it
``train``'s own code. ``correct``: every job ran its rounds with finite
losses and reported each; every client's loss of the checked round, the
MAIN part and the prediction module's part each, is within its limit of the
reference's, and the global parameters after it lie within a stated share of
the round's own movement from the reference's (``compare``); the same number
of compiles in every job; the run's data name the benchmark's generator.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from perfbench import datasets_lm, flops_xing4, reference_xing4
from perfbench.drivers.train import (_overlay, _traced, _window,
                                     experiment_config, job_faults, run_job,
                                     with_run)
from perfbench.drivers.train_nemotron_h import Ahead

# The published keys a configuration file states once, at its top level, and
# the program's ModelConfig takes under the same names; the nested
# ``rope_scaling`` group goes flat, each key behind ``rope_scaling_``.
MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "intermediate_size", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "first_k_dense_replace", "moe_intermediate_size",
              "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "num_nextn_predict_layers", "hc_mult",
              "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
              "mhc_h_res_clamp_max", "rms_norm_eps", "rope_theta",
              "vocab_size")
ROPE_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")
REFERENCE_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                  "norm_topk_prob", "routed_scaling_factor", "hc_mult",
                  "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                  "mhc_h_res_clamp_max", "rms_norm_eps", "rope_theta",
                  "rope_scaling", "mtp_loss_weight")

# The system's FIRST round against the plain reference (float32 at 'highest'
# precision, whole scores a block of heads at a time, the held experts
# densely, whole logits over the slice for both heads). Three numbers, each
# under a limit of its own (``compare``):
#
# * the largest absolute difference over the clients' losses of the checked
#   round, the main part and the prediction module's part each;
# * ``params_share``: how far the job's global parameters lie from the
#   reference's after the round, as a share of how far the reference's moved
#   from the start (both Euclidean norms over every parameter). A state left
#   unchanged reads 1, whatever the seed.
#
# ONE round is compared, where the two trajectories have not parted: this
# model's loss falls by a third of a unit a round and a step's gradients
# differ by 2% (median leaf) to 20% (expert and router leaves: tokens at the
# edge of the top four change experts) between bfloat16 and float32 inputs,
# so that in a SECOND round the losses differ by up to a tenth of a round's
# progress and the gaps are heavy-tailed (3.8e-3 to 5.4e-1 over twelve
# seeds; PERF.md, Findings, PR 37). The system computes its large matmuls,
# forward and backward, from bfloat16 inputs; the residual path, the router,
# the norms and RoPE are float32 on both sides. Read on the v5e at the
# published widths (my chip runs, PR 37; PERF.md, Findings, has every seed):
# main-loss gaps of 9.8e-4 to 4.0e-3 on losses near 9.95, module-loss gaps of
# 8.2e-4 to 1.9e-3 near 10.3, shares of 0.0100 to 0.0188 over eleven seeds
# (the limits were set after the first four and not moved). The reference
# against itself with its matmul inputs rounded to bfloat16 reads 1.4e-3 /
# 2.0e-3 / 0.0149 (the program's own size: 1.8e-3 / 1.4e-3 / 0.0149 at that
# seed), to float8_e4m3fn 1.04 / 0.21 / 1.07, and a state left unchanged
# 1.0, all through ``compare``. Each limit is five times the program's
# largest reading and a tenth or less of float8's. A
# configuration's rehearsal block states its own limits for the float32
# walk-through on the CPU.
MAIN_LOSS_TOLERANCE = 2.0e-2
MTP_LOSS_TOLERANCE = 1.0e-2
PARAMS_SHARE_TOLERANCE = 1.0e-1


def round_program(ctx, cfg, dataset, width: int) -> Ahead:
    """``train_nemotron_h.round_program`` with this cell's account: the
    round program the jobs will run compiles on a thread of its own from the
    shapes of the experiment as ``run_experiment`` builds it, after the
    experiment's state has left the device; the thread's result is the
    compiler's account. ``total`` is the compiler's own peak
    (``peak_memory_in_bytes``) where it states one: for this program the sum
    arguments + outputs - aliased + temporaries reads above the chip's whole
    memory (``temporaries`` counts 12.8 GB where the buffer assignment's heap
    is 8.5 GiB; PERF.md section 6, PR 37), so the sum is kept beside it as
    ``sum`` and is the total only where no peak is stated."""
    t = time.perf_counter()
    from fedtpu.orchestration.loop import (build_experiment,
                                           compile_round_program)
    ctx.clocks["program_import_s"] = time.perf_counter() - t
    exp = build_experiment(cfg, dataset)
    step = exp.make_step(width)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (exp.state, exp.batch))
    del exp                     # the global and the momentum leave the device
    ctx.clocks["program_build_s"] = time.perf_counter() - t

    def footprint() -> dict:
        t = time.perf_counter()
        ma = compile_round_program(step, *shapes).memory_analysis()
        ctx.clocks["program_compile_s"] = time.perf_counter() - t
        return program_account(ma)

    return Ahead(footprint)


def program_account(ma) -> dict:
    """The parts of a compiled program's memory and their ``total``."""
    parts = {"arguments": int(ma.argument_size_in_bytes),
             "outputs": int(ma.output_size_in_bytes),
             "aliased": int(ma.alias_size_in_bytes),
             "temporaries": int(ma.temp_size_in_bytes)}
    parts["sum"] = (parts["arguments"] + parts["outputs"] - parts["aliased"]
                    + parts["temporaries"])
    parts["peak"] = int(getattr(ma, "peak_memory_in_bytes", 0) or 0)
    parts["total"] = parts["peak"] or parts["sum"]
    return parts


def model_fields(conf: dict) -> dict:
    """The program's ModelConfig fields from the configuration file: the
    published keys under their own names, and the share: the file's
    ``n_routed_experts`` is how many experts are HELD, the router's width is
    the published count, the first held expert is the layout's."""
    return {**{k: conf[k] for k in MODEL_KEYS},
            **{f"rope_scaling_{k}": conf["rope_scaling"][k] for k in ROPE_KEYS},
            "n_routed_experts": conf["published"]["n_routed_experts"],
            "experts_held": conf["n_routed_experts"],
            "first_expert": conf["layout"].get("first_expert", 0)}


def reference_config(conf: dict) -> dict:
    return {**{k: conf[k] for k in REFERENCE_KEYS},
            "first_expert": conf["layout"].get("first_expert", 0)}


def reference_step(ctx, cfg, conf) -> Ahead:
    """The reference's SGD step, compiling from shapes alone on a thread of
    its own: it needs no device."""
    from fedtpu.models.registry import build_model

    def compile_it():
        t = time.perf_counter()
        step = reference_xing4.compiled_step(
            jax.eval_shape(build_model(cfg.model)[0], jax.random.key(0)),
            jax.ShapeDtypeStruct((2, int(conf["dataset"]["sequence_length"])),
                                 np.int32),
            reference_config(conf), cfg.optim.learning_rate)
        ctx.clocks["reference_compile_s"] = time.perf_counter() - t
        return step

    return Ahead(compile_it)


def reference_rounds(cfg, conf, dataset, rounds: int, step):
    """``(losses {"loss", "main", "mtp"}: (rounds, C) each, global params
    after the rounds, the initial ones)`` of the plain reference, both sets
    of parameters on the host, from the initial parameters the program draws
    from ``fed.init_seed``."""
    from fedtpu.models.registry import build_model

    start = jax.tree.map(np.asarray, jax.jit(build_model(cfg.model)[0])(
        jax.random.key(cfg.fed.init_seed)))
    rows = [dataset.x_train[dataset.client_of_row == c]
            for c in range(cfg.shard.num_clients)]
    losses, glob = reference_xing4.fedavgm_rounds(
        start, rows, rounds, reference_config(conf),
        learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr,
        step=step)
    return losses, glob, start


def distance(a, b) -> float:
    """The Euclidean distance of two sets of parameters on the host: float32
    differences, their squares summed in float64."""
    return float(np.sqrt(sum(
        np.square(np.asarray(x) - np.asarray(y)).sum(dtype=np.float64)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))))


def compare(ours: dict, params, reference: dict, ref_params, start,
            limits: dict) -> dict:
    """The comparison that decides ``correct``, on host values alone.
    ``ours`` / ``reference``: ``{"loss", "main", "mtp"}``, each ``(rounds,
    C)``, the job's and the plain reference's; ``params`` / ``ref_params``
    the global parameters after those rounds (``params`` None where the job
    ran on: then the losses decide alone), ``start`` the ones both began
    from; ``limits``: ``{"main", "mtp", "params_share"}``. Returns the
    numbers, each beside its limit, and ``within``."""
    apart = {name: np.abs(np.asarray(ours[name]) - np.asarray(reference[name]))
             for name in ("loss", "main", "mtp")}
    found = {"rounds": len(apart["main"]),
             "loss_gap": float(apart["loss"].max()),
             "main_gap": float(apart["main"].max()),
             "mtp_gap": float(apart["mtp"].max()),
             "main_tolerance": limits["main"], "mtp_tolerance": limits["mtp"],
             "params_share_tolerance": limits["params_share"],
             "by_round": {name: gap.max(axis=1).tolist()
                          for name, gap in apart.items()}}
    finite = all(bool(np.all(np.isfinite(np.asarray(v))))
                 for v in (*ours.values(), *reference.values()))
    within = (finite and found["main_gap"] <= limits["main"]
              and found["mtp_gap"] <= limits["mtp"])
    if params is not None:
        moved = distance(ref_params, start)
        found["params_moved"] = moved
        found["params_apart"] = distance(params, ref_params)
        found["params_share"] = found["params_apart"] / max(moved, 1e-30)
        within = within and found["params_share"] <= limits["params_share"]
    return {**found, "within": bool(within)}


def job_losses(result, rounds: int) -> dict:
    """What the job's engine reported of its first ``rounds`` rounds: the
    loss that is differentiated and its two parts (the task's ``main_loss``
    and ``mtp_loss``), ``(rounds, C)`` each."""
    return {"loss": np.stack(result.loss[:rounds]),
            "main": np.stack(result.per_client_metrics["main_loss"][:rounds]),
            "mtp": np.stack(result.per_client_metrics["mtp_loss"][:rounds])}


def limits_of(conf: dict) -> dict:
    return {"main": conf.get("loss_tolerance", MAIN_LOSS_TOLERANCE),
            "mtp": conf.get("loss_tolerance", MTP_LOSS_TOLERANCE),
            "params_share": conf.get("params_share_tolerance",
                                     PARAMS_SHARE_TOLERANCE)}


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

    ctx.compiles.phase = "setup"
    # the experiment is built alone (under a compile's threads it takes a
    # minute, not seconds); then the round program and the reference's step
    # compile side by side, and the reference's round runs while they do
    program = round_program(ctx, cfg, dataset, width)
    step = reference_step(ctx, cfg, conf)
    k = int(traffic["check_rounds"])
    t = time.perf_counter()
    ref_losses, ref_params, start = reference_rounds(cfg, conf, dataset, k,
                                                     step.result())
    ctx.clocks["reference_s"] = time.perf_counter() - t
    del step
    gc.collect()
    ctx.memory["after_reference"] = ctx.peak_bytes()
    # what of the compile the reference's rounds did not cover
    t = time.perf_counter()
    ctx.memory["round_program"] = program.result()
    ctx.clocks["footprint_s"] = time.perf_counter() - t

    warm_rounds = int(traffic["warmup_rounds"])
    with jax.profiler.TraceAnnotation("warmup"):
        warm, warm_s, _ = run_job(ctx, with_run(cfg, warm_rounds), dataset,
                                  "warmup")
    ctx.clocks["warmup_job_s"] = warm_s
    same_end = warm_rounds == k         # the job ended where the reference did
    check = compare(job_losses(warm, k),
                    warm.final_params if same_end else None, ref_losses,
                    ref_params, start, limits_of(conf))
    check.update(loss_first_last=[float(np.mean(warm.loss[0])),
                                  float(np.mean(warm.loss[-1]))],
                 main_mtp_first=[float(np.mean(ref_losses[name][0]))
                                 for name in ("main", "mtp")])
    del ref_params, start
    source_ok = (warm.data.get("generator")
                 == f"perfbench.{conf['dataset']['generator']}")
    faults = job_faults(warm, warm_rounds)
    correct = check["within"] and source_ok and faults == 0
    steady = warm.sec_per_round[width:] or warm.sec_per_round
    lines.append({"check": check, "data": warm.data, "source_ok": source_ok,
                  "counts": counts,
                  "warmup": {"rounds": warm_rounds, "seconds": warm_s,
                             "sec_per_round": float(np.median(steady)),
                             "faults": faults}})
    del warm
    gc.collect()
    ctx.memory["after_warmup"] = ctx.peak_bytes()
    ctx.clocks["setup_s"] = time.perf_counter() - ctx.t0
    ctx.compiles.phase = "between"

    model = model_fields(conf)
    cost = flops_xing4.round_cost(model, counts, clients)
    ctx.evidence.facts.update(cost=cost, chips=cell["chips"], width=width,
                              model=model)
    lines.append({"cost": cost})

    if not ctx.trace:
        out = _window(ctx, cfg, dataset, width, lines)
    else:
        out = _traced(ctx, cfg, dataset, width, traffic, lines)
        # the registry counts over every round of the traced job
        traced = next(l["traced_job"] for l in lines if "traced_job" in l)
        ctx.evidence.facts["job_rounds"] = traced["rounds"]
        ctx.evidence.facts["lm_positions"] = (traced["rounds"]
                                              * counts["positions"])
    out["correct"] = bool(correct and out["correct"])
    out["lines"] = lines
    return out
