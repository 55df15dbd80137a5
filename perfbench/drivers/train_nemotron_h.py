"""Driver ``train_nemotron_h``: federated jobs of the hybrid (``nemotron_h``)
tower through the program's normal path,
``fedtpu.orchestration.loop.run_experiment``.

``train_lm``'s flow with this model's configuration keys, reference and
cost: set-up (the experiment configuration FIRST, so that a program that
has no such model fails in seconds; then the corpus from ``--seed`` over the
vocabulary slice, the plain reference's rounds, one warm-up job that ends
where the reference does, the round program's footprint), then the window's
jobs or the traced job, all of it ``train``'s own code. ``correct``: every
job ran its rounds with finite losses and reported each; every client's
loss of the checked rounds and the global parameters after them are within
tolerance of the reference; the same number of compiles in every job; the
run's data name the benchmark's generator.

What set-up does at once, because a run has a time limit and this round
program is 0.85 GB of executable that takes two to three minutes to compile:
once the experiment is built and its state has left the device (the device
is handed over once), the round program compiles on a thread of its own
(its account is the footprint) while the reference compiles its step and
holds the device for its rounds. The jobs then run the executable the thread
compiled (``loop.compile_round_program``: the program keeps the newest round
program of a process and dispatches the executable compiled ahead for it).
"""

from __future__ import annotations

import gc
import threading
import time

import jax
import numpy as np

from perfbench import datasets_lm, flops_nemotron_h, reference_nemotron_h
from perfbench.drivers.train import (_overlay, _traced, _window,
                                     experiment_config, job_faults, run_job,
                                     with_run)

# The published keys a configuration file states once, at its top level, and
# the program's ModelConfig takes under the same names.
MODEL_KEYS = ("hidden_size", "num_hidden_layers", "hybrid_override_pattern",
              "layer_norm_epsilon", "mamba_num_heads", "mamba_head_dim",
              "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
              "time_step_min", "time_step_max", "time_step_floor",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "vocab_size")
REFERENCE_KEYS = ("hybrid_override_pattern", "layer_norm_epsilon",
                  "mamba_num_heads", "mamba_head_dim", "n_groups",
                  "ssm_state_size", "num_attention_heads",
                  "num_key_value_heads", "head_dim", "num_experts_per_tok",
                  "norm_topk_prob", "routed_scaling_factor")

# The system's first rounds against the plain reference (float32 at 'highest'
# precision, the state-space layer token by token, the held experts densely,
# whole logits over the slice): the largest absolute difference over all
# clients' losses of the checked rounds, and over every global parameter
# after them. The system computes its large matmuls and the scan's chunk
# products, forward and backward, from bfloat16 inputs. Measured on the v5e
# at the published widths (my chip runs, PR 32; PERF.md, Findings, has them
# over eleven seeds): loss gaps of 3.0e-4 to 9.5e-4 on losses near 10.2,
# parameter gaps of 6.4e-6 to 8.3e-6 on parameters that moved by 1.9e-3 to
# 2.5e-3 in the two rounds. The tolerances are four times the largest gap
# seen: the reference itself with its matmul inputs rounded to bfloat16
# reads 1.12e-3 / 6.8e-6 (inside both), to float8_e4m3fn 1.50e-1 / 3.1e-4
# (outside both). They hold the algorithm (which rows a client holds, the order of its
# steps, the weights of the mean, the server's momentum, the share of the
# experts) and the precision: PERF.md has the reference's own readings with
# its matmul inputs rounded to bfloat16 and to float8_e4m3fn. A
# configuration's rehearsal block states its own for the float32
# walk-through on the CPU.
LOSS_TOLERANCE = 3.8e-3
PARAMS_TOLERANCE = 3.3e-5


def params_gap(a, b) -> float:
    """``train.params_gap``'s number, the largest absolute difference over
    every parameter, without its float64 copies (a quarter of a minute for
    this model on a shared host): the difference of two float32 numbers
    within a factor of two of each other is exact, and elsewhere it is
    rounded to a part in 2^24 of itself."""
    return max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def model_fields(conf: dict) -> dict:
    """The program's ModelConfig fields from the configuration file: the
    published keys under their own names, and the share: the file's
    ``n_routed_experts`` is how many experts are HELD, the router's width is
    the published count, the first held expert is the layout's."""
    return {**{k: conf[k] for k in MODEL_KEYS},
            "n_routed_experts": conf["published"]["n_routed_experts"],
            "experts_held": conf["n_routed_experts"],
            "first_expert": conf["layout"].get("first_expert", 0)}


class Ahead(threading.Thread):
    """``fn()`` on a thread of its own: ``result()`` waits for it and gives
    what it returned, or raises what it raised."""

    def __init__(self, fn):
        super().__init__(name="ahead", daemon=True)
        self.fn, self.out, self.error = fn, None, None
        self.start()

    def run(self):
        try:
            self.out = self.fn()
        except BaseException as err:    # raised where the result is asked for
            self.error = err

    def result(self):
        self.join()
        if self.error is not None:
            raise self.error
        return self.out


def round_program(ctx, cfg, dataset, width: int) -> Ahead:
    """The round program the jobs will run, compiling on a thread of its
    own from the shapes of the experiment as ``run_experiment`` builds it
    (``loop.compile_round_program``: the jobs dispatch this executable).
    The experiment's state has left the device when this returns; the
    thread's result is the compiler's account, as ``train.program_footprint``
    gives it."""
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
        parts = {"arguments": int(ma.argument_size_in_bytes),
                 "outputs": int(ma.output_size_in_bytes),
                 "aliased": int(ma.alias_size_in_bytes),
                 "temporaries": int(ma.temp_size_in_bytes)}
        parts["total"] = (parts["arguments"] + parts["outputs"]
                          - parts["aliased"] + parts["temporaries"])
        return parts

    return Ahead(footprint)


def reference_rounds(cfg, conf, dataset, rounds: int):
    """``(losses (rounds, C), global params, how far they moved)`` of the
    plain reference from the initial parameters the program draws from
    ``fed.init_seed``: drawn on the device when the rounds start, and once
    more after them for the distance, so that no copy waits anywhere."""
    from fedtpu.models.registry import build_model

    draw = jax.jit(build_model(cfg.model)[0])
    init = lambda: draw(jax.random.key(cfg.fed.init_seed))
    rows = [dataset.x_train[dataset.client_of_row == c]
            for c in range(cfg.shard.num_clients)]
    share = {"first_expert": conf["layout"].get("first_expert", 0)}
    losses, glob = reference_nemotron_h.fedavgm_rounds(
        init, rows, rounds, {**{k: conf[k] for k in REFERENCE_KEYS}, **share},
        learning_rate=cfg.optim.learning_rate,
        momentum=cfg.fed.server_momentum, server_lr=cfg.fed.server_lr)
    return losses, glob, params_gap(glob, init())


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
    program = round_program(ctx, cfg, dataset, width)
    k = int(traffic["check_rounds"])
    t = time.perf_counter()
    ref_losses, ref_params, moved = reference_rounds(cfg, conf, dataset, k)
    ctx.clocks["reference_s"] = time.perf_counter() - t
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
    ctx.clocks["setup_s"] = time.perf_counter() - ctx.t0
    ctx.compiles.phase = "between"

    model = model_fields(conf)
    cost = flops_nemotron_h.round_cost(model, counts, clients)
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
