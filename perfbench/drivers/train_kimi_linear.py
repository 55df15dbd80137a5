"""Driver ``train_kimi_linear``: federated jobs of the Kimi-Linear stack (a
gated delta-rule recurrence three layers in four, latent attention without
positions, a leading dense layer, held experts) through the program's normal
path, ``fedtpu.orchestration.loop.run_experiment``.

``train_xing4``'s flow with this model's configuration keys, reference and
cost: set-up (the experiment configuration FIRST, so that a program that has
no such model fails in seconds; then the corpus from ``--seed`` over the
vocabulary slice, the round program compiling on a thread of its own while
the plain reference runs its round, one warm-up job that ends where the
reference does), then the window's jobs or the traced job, all of it
``train``'s own code. ``correct``: every job ran its rounds with finite
losses and reported each; every client's loss of the checked round is
within its limit of the reference's, and the global parameters after it lie
within a stated share of the round's own movement from the reference's
(``compare``); the same number of compiles in every job; the run's data name
the benchmark's generator.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from perfbench import datasets_lm, flops_kimi_linear, reference_kimi_linear
from perfbench.drivers.train import (_overlay, _traced, _window,
                                     experiment_config, job_faults, run_job,
                                     with_run)
from perfbench.drivers.train_nemotron_h import Ahead
from perfbench.drivers.train_xing4 import distance, round_program

# The published keys a configuration file states once, at its top level, and
# the program's ModelConfig takes under the same names; four go by the names
# the program's expert layer already reads, and the nested
# ``linear_attn_config`` group goes flat (``model_fields``).
MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "intermediate_size", "first_k_dense_replace", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "mla_use_nope", "moe_intermediate_size",
              "routed_scaling_factor", "num_nextn_predict_layers",
              "rms_norm_eps", "rope_theta", "vocab_size")
RENAMED = {"num_experts_per_token": "num_experts_per_tok",
           "num_shared_experts": "n_shared_experts",
           "moe_renormalize": "norm_topk_prob"}
REFERENCE_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "num_experts_per_token",
                  "moe_renormalize", "routed_scaling_factor", "rms_norm_eps",
                  "linear_attn_config")

# The system's FIRST round against the plain reference (float32 at 'highest'
# precision, the recurrence token by token, whole scores a block of heads at
# a time, the held experts densely, whole logits over the slice). Two
# numbers, each under a limit of its own (``compare``):
#
# * the largest absolute difference over the clients' losses of the checked
#   round;
# * ``params_share``: how far the job's global parameters lie from the
#   reference's after the round, as a share of how far the reference's moved
#   from the start (both Euclidean norms over every parameter). A state left
#   unchanged reads 1, whatever the seed.
#
# ONE round is compared, where the two trajectories have not parted (tokens
# at the edge of the top eight change experts between bfloat16 and float32
# inputs, as in the Xing4.0 cell). The system computes its large matmuls and
# the chunk products of the recurrence, forward and backward, from bfloat16
# inputs; the state, the decays, the triangular inverse, the router and the
# norms are float32 on both sides. Read on the v5e at the published widths
# (my chip runs, PR 39; PERF.md, Findings, has every seed): over thirteen
# seeds loss gaps of 2.7e-4 to 1.34e-3 on losses near 10.3 and one of
# 4.08e-3 (a heavy tail, as the Xing4.0 cell's: a token at the edge of the
# top eight moves one client's loss), shares of 0.0180 to 0.0204. Each limit
# is four times the largest reading. The reference against itself, through
# ``compare``: with its matmul inputs rounded to bfloat16 7.4e-4 / 0.0151
# (the program's own size), to float8_e4m3fn 7.4e-2 / 0.919, without the
# delta term 1.9e-1 / 0.956, a state left unchanged 0 / 1.0: each of the
# last three outside both limits. With a bfloat16 STATE in its recurrence it
# reads 2.8e-5 / 0.0054, INSIDE both and under the program's own reading:
# on the chip the rounding of every other matmul's inputs is the larger
# part of the distance, so this comparison does not tell that variant; the
# float32 walk-through does (0.076 against its limit of 0.001, and tier-1's
# two rounds at 2e-5). A configuration's rehearsal block states its own
# limits for the float32 walk-through on the CPU.
LOSS_TOLERANCE = 1.6e-2
PARAMS_SHARE_TOLERANCE = 8.2e-2


def model_fields(conf: dict) -> dict:
    """The program's ModelConfig fields from the configuration file: the
    published keys under their own names or the program's name for them, the
    ``linear_attn_config`` group flat, and the share: the file's
    ``num_experts`` is how many experts are HELD, the router's width is the
    published count, the first held expert is the layout's. ``rope_scaling``
    is null in the published config: no factor, so the softmax scale is the
    plain ``(nope + rope)^-1/2``."""
    lin = conf["linear_attn_config"]
    return {**{k: conf[k] for k in MODEL_KEYS},
            **{ours: conf[theirs] for theirs, ours in RENAMED.items()},
            "kda_layers": tuple(lin["kda_layers"]),
            "full_attn_layers": tuple(lin["full_attn_layers"]),
            "kda_num_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
            "short_conv_kernel_size": lin["short_conv_kernel_size"],
            "rope_scaling_factor": 1.0,
            "n_routed_experts": conf["published"]["num_experts"],
            "experts_held": conf["num_experts"],
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
        step = reference_kimi_linear.compiled_step(
            jax.eval_shape(build_model(cfg.model)[0], jax.random.key(0)),
            jax.ShapeDtypeStruct((2, int(conf["dataset"]["sequence_length"])),
                                 np.int32),
            reference_config(conf), cfg.optim.learning_rate)
        ctx.clocks["reference_compile_s"] = time.perf_counter() - t
        return step

    return Ahead(compile_it)


def reference_rounds(cfg, conf, dataset, rounds: int, step):
    """``(losses (rounds, C), global params after the rounds, the initial
    ones)`` of the plain reference, both sets of parameters on the host, from
    the initial parameters the program draws from ``fed.init_seed``."""
    from fedtpu.models.registry import build_model

    start = jax.tree.map(np.asarray, jax.jit(build_model(cfg.model)[0])(
        jax.random.key(cfg.fed.init_seed)))
    rows = [dataset.x_train[dataset.client_of_row == c]
            for c in range(cfg.shard.num_clients)]
    losses, glob = reference_kimi_linear.fedavgm_rounds(
        start, rows, rounds, reference_config(conf),
        learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr,
        step=step)
    return losses, glob, start


def compare(ours, params, reference, ref_params, start, limits: dict) -> dict:
    """The comparison that decides ``correct``, on host values alone. ``ours``
    / ``reference``: ``(rounds, C)`` losses, the job's and the plain
    reference's; ``params`` / ``ref_params`` the global parameters after
    those rounds (``params`` None where the job ran on: then the losses
    decide alone), ``start`` the ones both began from; ``limits``: ``{"loss",
    "params_share"}``. Returns the numbers, each beside its limit, and
    ``within``."""
    ours, reference = np.asarray(ours), np.asarray(reference)
    apart = np.abs(ours - reference)
    found = {"rounds": len(apart), "loss_gap": float(apart.max()),
             "tolerance": limits["loss"],
             "params_share_tolerance": limits["params_share"],
             "by_round": apart.max(axis=1).tolist()}
    within = (bool(np.all(np.isfinite(ours)) and np.all(np.isfinite(reference)))
              and found["loss_gap"] <= limits["loss"])
    if params is not None:
        moved = distance(ref_params, start)
        found["params_moved"] = moved
        found["params_apart"] = distance(params, ref_params)
        found["params_share"] = found["params_apart"] / max(moved, 1e-30)
        within = within and found["params_share"] <= limits["params_share"]
    return {**found, "within": bool(within)}


def limits_of(conf: dict) -> dict:
    return {"loss": conf.get("loss_tolerance", LOSS_TOLERANCE),
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
    # the experiment is built alone; then the round program and the
    # reference's step compile side by side, and the reference's round runs
    # while they do
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
    check = compare(np.stack(warm.loss[:k]),
                    warm.final_params if same_end else None, ref_losses,
                    ref_params, start, limits_of(conf))
    check["loss_first_last"] = [float(np.mean(warm.loss[0])),
                                float(np.mean(warm.loss[-1]))]
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
    cost = flops_kimi_linear.round_cost(model, counts, clients)
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
        # a row passes every KDA layer once a round
        ctx.evidence.facts["kda_rows"] = (traced["rounds"] * counts["sequences"]
                                          * len(model["kda_layers"]))
    out["correct"] = bool(correct and out["correct"])
    out["lines"] = lines
    return out
