"""Driver ``train_solar_open2``: federated jobs of the Solar-Open2 stack (a
gated grouped-query softmax layer without positions leading every four, KDA
with ``beta = 2 sigmoid`` in the other three, held experts beside a shared
one) through the program's normal path,
``fedtpu.orchestration.loop.run_experiment``.

``train_kimi_linear``'s flow with this model's configuration keys, reference
and cost: set-up (the experiment configuration FIRST, so that a program that
has no such model fails in seconds; then the corpus from ``--seed`` over the
vocabulary slice, the round program compiling on a thread of its own while
the plain reference runs its round, one warm-up job that ends where the
reference does), then the window's jobs or the traced job, all of it
``train``'s own code. ``correct``: every job ran its rounds with finite
losses and reported each; every client's loss of the checked round is within
its limit of the reference's, and the global parameters after it lie within
a stated share of the round's own movement from the reference's
(``train_kimi_linear.compare``); the same number of compiles in every job;
the run's data name the benchmark's generator.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from perfbench import datasets_lm, flops_solar_open2, reference_solar_open2
from perfbench.drivers.train import (_overlay, _traced, _window,
                                     experiment_config, job_faults, run_job,
                                     with_run)
from perfbench.drivers.train_kimi_linear import compare
from perfbench.drivers.train_nemotron_h import Ahead
from perfbench.drivers.train_xing4 import round_program

# The published keys a configuration file states once, at its top level, and
# the program's ModelConfig takes under the same names; the nested
# ``linear_attn_config`` group goes flat (``model_fields``).
MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "first_k_dense_replace", "moe_intermediate_size",
              "num_experts_per_tok", "n_shared_experts", "norm_topk_prob",
              "routed_scaling_factor", "rms_norm_eps", "vocab_size",
              "use_gqa_gate", "kda_allow_neg_eigval")
REFERENCE_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
                  "num_experts_per_tok", "norm_topk_prob",
                  "routed_scaling_factor", "rms_norm_eps", "use_gqa_gate",
                  "kda_allow_neg_eigval", "linear_attn_config")
# What the stack does not build: a configuration that asks for it is refused.
UNBUILT = {"use_rope": False, "kda_use_full_proj": False}

# The system's FIRST round against the plain reference (float32 at 'highest'
# precision, the recurrence token by token with beta = 2 sigmoid, whole
# scores a block of heads at a time times the sigmoid gate, the held experts
# densely, whole logits over the slice). Two numbers, each under a limit of
# its own (``train_kimi_linear.compare``):
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
# inputs, as in the Kimi-Linear cell). The system computes its large matmuls
# and the chunk products of the recurrence from bfloat16 inputs; the state,
# the decays, the triangular inverse, the router, the norms and both sigmoid
# gates are float32 on both sides. How the limits were derived from readings
# on the v5e at the published widths (the seeds, the readings and the
# controls: PERF.md section 6, PR 47): each lies between the largest reading
# of the program over its seeds and what the reference reads against itself
# with its products' inputs rounded to float8_e4m3fn, the nearest precision
# below bfloat16, with room on both sides. A configuration's rehearsal block
# states its own limits for the float32 walk-through on the CPU.
LOSS_TOLERANCE = 1.6e-2
PARAMS_SHARE_TOLERANCE = 2.0e-1


def model_fields(conf: dict) -> dict:
    """The program's ModelConfig fields from the configuration file: the
    published keys under their own names, the ``linear_attn_config`` group
    flat, ``gqa_layers`` 0-based as published, and the share: the file's
    ``n_routed_experts`` is how many experts are HELD, the router's width is
    the published count, the first held expert is the layout's; the heads
    the file states are the heads held."""
    for key, built in UNBUILT.items():
        if conf[key] != built:
            raise ValueError(f"{key} {conf[key]!r}: the stack builds "
                             f"{built!r} alone")
    lin = conf["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("linear_attn_config.num_kv_heads: KDA's keys and "
                         "values have the queries' heads")
    return {**{k: conf[k] for k in MODEL_KEYS},
            "gqa_layers": tuple(conf["gqa_layers"]),
            "kda_num_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
            "short_conv_kernel_size": lin["short_conv_kernel_size"],
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
        step = reference_solar_open2.compiled_step(
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
    losses, glob = reference_solar_open2.fedavgm_rounds(
        start, rows, rounds, reference_config(conf),
        learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr,
        step=step)
    return losses, glob, start


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
    cost = flops_solar_open2.round_cost(model, counts, clients)
    ctx.evidence.facts.update(cost=cost, chips=cell["chips"], width=width,
                              model=model)
    lines.append({"cost": cost})

    if not ctx.trace:
        out = _window(ctx, cfg, dataset, width, lines)
    else:
        out = _traced(ctx, cfg, dataset, width, traffic, lines)
        # the registry counts over every round of the traced job
        traced = next(l["traced_job"] for l in lines if "traced_job" in l)
        rounds = traced["rounds"]
        kda_layers = flops_solar_open2._layers(model)["kda"]
        ctx.evidence.facts["job_rounds"] = rounds
        ctx.evidence.facts["lm_positions"] = rounds * counts["positions"]
        # a row passes every KDA layer once a round
        ctx.evidence.facts["kda_rows"] = (rounds * counts["sequences"]
                                          * kda_layers)
        # the delta rule's steps the PUBLISHED heads would take over the
        # same real tokens: what the heads held are a share of
        ctx.evidence.facts["so2_published_head_steps"] = (
            rounds * counts["tokens"] * kda_layers
            * conf["published"]["linear_attn_config"]["num_heads"])
    out["correct"] = bool(correct and out["correct"])
    out["lines"] = lines
    return out
