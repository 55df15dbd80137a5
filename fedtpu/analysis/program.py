"""SPMD program auditor (``fedtpu audit``): static contracts for the
round programs.

Where ``fedtpu lint`` reads source and ``fedtpu check`` drives the
compiled step, this sits between them: it traces the *real* engine
programs — the 1-D shard_map round (``parallel/round.py``), the FedBuff
tick (``parallel/async_fed.py``), the 2-D GSPMD round
(``parallel/tp.py``) and the scan-over-cohorts chunk
(``cohort/scheduler.py``) — and proves three properties on the IR
without spending a device cycle:

  * **Collective schedule** (collectives.py): the ordered psum /
    all_gather / ppermute sequence with axis names, per-device operand
    bytes, and scan trip counts, identical across every config-reachable
    ``cond`` branch (``AUD001`` otherwise — the static form of the gang
    hang PR 5's watchdog can only time out on).
  * **Donation realization**: every ``donate_argnums`` buffer actually
    aliased to an output in the lowered module (``tf.aliasing_output``
    arg attributes), turning the FTP003 AST heuristic into a proof;
    ``AUD002`` names each donated-but-copied leaf.
  * **Comm-byte account + surfaces**: the per-round statically-counted
    communication bytes (ROADMAP item 2's byte-bound gap, quantified), a
    recompile-surface fingerprint over the argument avals, and the
    nondeterministic-op census.

The per-preset contract is JSON-stable: ``tests/goldens/audit_*.json``
pins it and ``tests/test_audit_gate.py`` fails tier-1 on any silent
collective addition, donation loss, or byte inflation.  Contracts are
shape-deterministic given (preset, synthetic_rows, device_count) — the
goldens record the 8-virtual-device test topology.

For the 2-D engine the jaxpr level is intentionally collective-free
(GSPMD chooses the collectives after partitioning), so its contract
additionally carries a compiled-HLO collective census — the only probe
here that pays a compile.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import re
from typing import Any, Iterable, Optional, Sequence

from fedtpu.analysis.collectives import (AuditFinding, comm_bytes,
                                         extract_schedule, schedule_digest)

__all__ = [
    "AUDIT_ENGINES",
    "audit_preset",
    "audit_program",
    "audit_step_summary",
    "diff_audit",
    "donation_proof",
    "engine_audit_spec",
    "program_scopes",
    "render_audit_text",
]

AUDIT_VERSION = 1
AUDIT_ENGINES = ("sync", "async", "tp", "cohort")

# One instruction a line: `%name = <type> <opcode>(operands...)`. The type
# of a combined collective is a tuple with spaces in it, so the opcode is
# found as the first ` <opcode>(` after the `=`, whatever stands between.
_HLO_COLLECTIVE_RE = re.compile(
    r"^[^=\n]*= .*? (all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)(?:-start)?\(", re.MULTILINE
)


# ---------------------------------------------------------------------------
# stage scopes of a compiled program
# ---------------------------------------------------------------------------

_HLO_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_HLO_INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_HLO_OPCODE_RE = re.compile(r"[\]})] ([a-z][\w\-]*)\(")
_HLO_SHAPE_RE = re.compile(r"[a-z][a-z0-9]*\[[\d,]*\]")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
# Computations a thunk runs instruction by instruction (what a device
# trace lists), as opposed to fusion bodies and reducers.
_HLO_CONTROL_RE = re.compile(
    r"(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\}")
_HLO_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_HLO_OPERAND_RE = re.compile(r"%([\w.\-]+)")
# Never executed as an operation of their own: no trace lists them.
_HLO_NO_OP = frozenset({"parameter", "constant", "get-tuple-element",
                        "tuple", "bitcast"})
# What every stage's values pass through: no stage is inherited across.
_HLO_JUNCTION = frozenset({"parameter", "tuple", "get-tuple-element",
                           "while", "conditional", "call"})


def _components(op_name: str) -> list:
    """The scopes on a name stack such as
    ``jit(round_step)/jit(shmap_body)/while/body/client_train/vmap(...)``,
    outermost first, each out of the transforms that wrap its component
    (``transpose(jvp(aggregate))`` is ``aggregate``)."""
    return [part.rsplit("(", 1)[-1].rstrip(")")
            for part in op_name.split("/")]


def _stage_of(op_name: str, stages: Sequence[str]) -> Optional[str]:
    """Innermost of the scopes ``stages`` on ``op_name``."""
    for inner in reversed(_components(op_name)):
        if inner in stages:
            return inner
    return None


# The directions ``program_scopes`` tells apart under ``passes``.
FORWARD, RECOMPUTE, BACKWARD, UPDATE = PASSES = (
    "forward", "recompute", "backward", "update")
# What ``jax.checkpoint``'s backward rule (jax 0.9.0, ``ad_checkpoint``:
# ``remat_partial_eval`` / the ``remat2`` lowering) puts on the name stack of
# the forward body it runs again; the body's transposed operations stand
# beside it under ``checkpoint/`` alone.
_REMAT_RECOMPUTED = "rematted_computation"


def _pass_of(op_name: str, update: Sequence[str],
             recompute: Sequence[str]) -> str:
    """The direction an operation named ``op_name`` runs in: the rule of
    ``program_scopes``' ``passes``."""
    inner = _components(op_name)
    if any(scope in update for scope in inner):
        return UPDATE
    # ``transpose(recompute)`` is the backward pass of what was run again
    if any((scope == _REMAT_RECOMPUTED or scope in recompute)
           and "transpose(" not in part
           for part, scope in zip(op_name.split("/"), inner)):
        return RECOMPUTE
    if "transpose(" in op_name:        # the primitive's own name has no "("
        return BACKWARD
    return FORWARD


def program_scopes(compiled_text: str, stages: Sequence[str],
                   strict: bool = False, *, layers: Sequence[str] = (),
                   pieces: Sequence[str] = (), modules: Sequence[str] = (),
                   update: Sequence[str] = (),
                   recompute: Sequence[str] = ()) -> dict:
    """Which of the ``jax.named_scope`` names ``stages`` (the builder's:
    ``parallel.round.STAGES``) each operation of a compiled program belongs
    to, from ``Compiled.as_text()``: ``{"scopes": {key: scope}, "unscoped":
    [key], "layers", "pieces", "passes"}``, all from one walk of the text. A
    key is ``"<instruction> <first result shape>"`` (at most
    120 characters), which is how a profiler trace's ``XLA Ops`` event
    reads once cut to its name and shape: the trace carries the HLO text
    of an instruction and no ``op_name``, so only the program can say
    which instruction is whose. Listed are the instructions of the entry
    computation and of every computation run through control flow (a
    scanned body, a branch, an async call). An instruction takes the
    innermost stage on its own ``op_name``, which for a fusion is its
    root's: a fusion that XLA formed across two stages counts for the
    stage of its root. One the compiler made without an ``op_name`` (a
    copy, a prefetch into faster memory, a decomposed dot) takes the stage
    all its users carry, else the one all its operands carry; nothing is
    inherited across a loop, a branch, a tuple or a parameter.
    ``strict``: only an instruction WITHOUT an ``op_name`` inherits. For
    the stages, which cover a program, the difference is nil; for a
    level of scopes that covers only parts of it an
    instruction that names itself outside every scope (the optimizer's
    update of a weight) stays outside, whoever made its operands.

    ``layers`` and ``pieces`` (``parallel.round.LAYERS``, ``PIECES``) are
    two such levels: ``{key: innermost scope of the level}``, both strict,
    empty where the program names none or none is asked for. ``modules``
    (``parallel.round.MODULES``) is a third, of scopes that lie AROUND
    layers (a whole multi-token-prediction module): read as ``layers`` is,
    and given only where it is asked for.

    A fusion the compiler left without an ``op_name`` (a multi-output
    fusion: its root is a tuple, which carries none) reads, for ``pieces``
    and ``passes``, what the named instructions of its body agree on, and
    inherits only where they do not; ``scopes`` and ``layers`` read it by
    its neighbours, as they always have.

    ``passes`` gives every key the direction it runs in, read from the
    same ``op_name``, first match: a scope of ``update`` anywhere on the
    stack (the engine's ``sgd_pass``, ``server_update``) is ``"update"``;
    else ``rematted_computation`` (what ``jax.checkpoint``'s backward rule
    names the forward body it runs again; its transposed operations stand
    beside it under ``checkpoint/`` alone) or a scope of ``recompute`` (a
    rule that runs a forward pass again by hand says so itself; the
    transposed operations of what it ran, ``transpose(recompute)/...``, are
    not) is ``"recompute"``; else a component wrapped in ``transpose(...)``
    (``transpose(jvp(client_train))``, ``vmap(transpose(jvp()))``, a custom
    rule's ``transpose(client_train)/jvp(experts)``) is ``"backward"``; else
    (``jvp(...)`` alone, or no transform) ``"forward"``. The map says where
    an operation runs, not what it computes: a custom rule that makes
    gradients in its forward pass reads ``forward``. An instruction without
    ``op_name`` inherits as ``strict`` does, and reads ``forward`` where
    its neighbours disagree."""
    computations: dict[str, list[str]] = {}
    entry = current = None
    for line in compiled_text.splitlines():
        head = _HLO_COMPUTATION_RE.match(line)
        if head:
            current = head.group(1)
            computations[current] = []
            if line.startswith("ENTRY"):
                entry = current
        elif current is not None and line.startswith(" "):
            computations[current].append(line)
    run = {entry}
    for lines in computations.values():
        for line in lines:
            for one, many in _HLO_CONTROL_RE.findall(line):
                run.update(n.strip().lstrip("%")
                           for n in (one or many).split(","))
            opcode = _HLO_OPCODE_RE.search(line)
            if opcode and opcode.group(1) != "fusion":
                run.update(_HLO_CALLS_RE.findall(line))
    # one resolver a map: how an op_name reads, who may inherit, and
    # whether a fusion without a name reads its body
    levels = [("scopes", lambda name: _stage_of(name, stages), strict, False)]
    if layers:
        levels.append(
            ("layers", lambda name: _stage_of(name, layers), True, False))
    if pieces:
        levels.append(
            ("pieces", lambda name: _stage_of(name, pieces), True, True))
    if modules:
        levels.append(
            ("modules", lambda name: _stage_of(name, modules), True, False))
    levels.append(
        ("passes", lambda name: _pass_of(name, update, recompute), True, True))
    found: dict[str, dict] = {"scopes": {}, "layers": {}, "pieces": {},
                              "passes": {}, **({"modules": {}} if modules
                                               else {})}
    unscoped: list[str] = []
    for name in run:
        keys, op_names, operands, junctions, bodies = {}, {}, {}, set(), {}
        for line in computations.get(name, ()):
            inst = _HLO_INSTRUCTION_RE.match(line)
            opcode = inst and _HLO_OPCODE_RE.search(inst.group(2))
            if not opcode:
                continue
            inst, rest = inst.groups()
            if opcode.group(1) not in _HLO_NO_OP:
                shape = _HLO_SHAPE_RE.search(rest)
                keys[inst] = (inst
                              + (" " + shape.group(0) if shape else ""))[:120]
            op_name = _HLO_OP_NAME_RE.search(rest)
            op_names[inst] = op_name.group(1) if op_name else None
            if not op_name and opcode.group(1) == "fusion":
                bodies[inst] = [
                    n for body in _HLO_CALLS_RE.findall(rest)
                    for n in _HLO_OP_NAME_RE.findall(
                        "\n".join(computations.get(body, ())))]
            if opcode.group(1) in _HLO_JUNCTION:
                junctions.add(inst)
            operands[inst] = _HLO_OPERAND_RE.findall(rest[opcode.end():])
        users: dict[str, list[str]] = {inst: [] for inst in op_names}
        for inst, ops in operands.items():
            for op in ops:
                if op in users:
                    users[op].append(inst)
        # HLO text defines an instruction before its users, so one pass in
        # the right order resolves whole chains
        defined = list(op_names)
        for level, read, own_name_only, read_body in levels:
            stage = {inst: read(op_name) if op_name else None
                     for inst, op_name in op_names.items()}
            if read_body:
                for inst, names in bodies.items():
                    inside = set(map(read, names)) - {None}
                    if len(inside) == 1:
                        stage[inst] = inside.pop()
            for order, edges in ((reversed(defined), users),
                                 (defined, operands)):
                for inst in order:
                    if (stage[inst] is None and inst not in junctions
                            and not (own_name_only and op_names[inst])):
                        near = {stage[n] for n in edges[inst] if n in stage}
                        near.discard(None)
                        if len(near) == 1:
                            stage[inst] = near.pop()
            for inst, key in keys.items():
                if stage[inst]:
                    found[level][key] = stage[inst]
                elif level == "scopes":
                    unscoped.append(key)
                elif level == "passes":
                    found[level][key] = FORWARD
    return {**found, "unscoped": sorted(unscoped)}


# ---------------------------------------------------------------------------
# donation proof
# ---------------------------------------------------------------------------


_IO_ALIAS_RE = re.compile(r"\{[\d, ]*\}:\s*\((\d+),\s*\{[\d, ]*\},\s*"
                          r"(?:may|must)-alias\)")


def _aliased_arg_indices(compiled_text: str) -> Optional[set]:
    """Flat parameter indices realized as input/output aliases in the
    compiled executable's entry module header
    (``input_output_alias={ {out}: (param, {}, may-alias), ... }``).

    This reads the *compiled* HLO, not the StableHLO lowering: for
    sharded programs jax lowers donation to a ``jax.buffer_donor``
    *hint* and XLA decides the actual aliasing after SPMD partitioning
    — only the executable header proves the buffer is reused.  Returns
    None when no entry-module header is found (callers degrade to
    'unproven', never to a false pass)."""
    hdr = next((ln for ln in compiled_text.splitlines()
                if ln.startswith("HloModule")), None)
    if hdr is None:
        return None
    return {int(m.group(1)) for m in _IO_ALIAS_RE.finditer(hdr)}


def _flat_args_with_paths(args: Sequence[Any]):
    """Flattened (top-level argnum, key path, leaf) in the order the
    lowered module's %argN parameters take."""
    import jax

    out = []
    for i, a in enumerate(args):
        paths, _ = jax.tree_util.tree_flatten_with_path(a)
        for p, leaf in paths:
            out.append((i, jax.tree_util.keystr(p) or "<leaf>", leaf))
    return out


def donation_proof(compiled_text: str, args: Sequence[Any],
                   donate_argnums: Sequence[int],
                   alias_expected: Optional[Sequence[int]] = None,
                   min_bytes: int = 1024) -> dict:
    """Prove (or refute) donation per donated leaf from compiled HLO.

    Returns ``{"argnums", "table", "ok", "findings"}`` where each table
    row is one donated leaf with its realized-alias bit (the goldens pin
    the whole table, so ANY lost alias is a contract diff).  ``findings``
    raises AUD002 only where the miss is an actual defect: the leaf
    belongs to an ``alias_expected`` arg (state carries the program
    threads back out — engines mark donate-to-free stream buffers, which
    have no output to alias, via their AUDIT_SPEC) and is at least
    ``min_bytes`` big (XLA occasionally declines sub-KiB aliases for
    layout reasons; those show in the table, not as defects).
    """
    aliased = _aliased_arg_indices(compiled_text)
    expected = set(donate_argnums if alias_expected is None
                   else alias_expected)
    table, findings = [], []
    for flat_idx, (argnum, path, leaf) in enumerate(_flat_args_with_paths(args)):
        if argnum not in donate_argnums:
            continue
        shape = tuple(int(d) for d in getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", "?"))
        size = 1
        for d in shape:
            size *= d
        nbytes = size * int(getattr(getattr(leaf, "dtype", None),
                                    "itemsize", 4))
        ok = aliased is not None and flat_idx in aliased
        table.append({"arg": argnum, "leaf": path, "shape": list(shape),
                      "dtype": dtype, "bytes": nbytes, "aliased": ok})
        if not ok and argnum in expected and nbytes >= min_bytes:
            findings.append(AuditFinding(
                code="AUD002",
                message=(f"donated buffer arg{argnum}{path} "
                         f"({dtype}{list(shape)}, {nbytes}B) is NOT "
                         "aliased in the compiled executable — donation "
                         "unrealized, a full copy per step"),
            ))
    return {
        "argnums": sorted(int(i) for i in donate_argnums),
        "table": table,
        "ok": not findings,
        "findings": findings,
    }


# ---------------------------------------------------------------------------
# single-program audit
# ---------------------------------------------------------------------------


def _recompile_surface(args: Sequence[Any]) -> dict:
    """Fingerprint of the traced argument surface: any change to the
    leaf paths / shapes / dtypes here means the next call retraces."""
    rows = [[path, [int(d) for d in getattr(leaf, "shape", ())],
             str(getattr(leaf, "dtype", "?"))]
            for _, path, leaf in _flat_args_with_paths(args)]
    digest = hashlib.sha256(
        json.dumps(rows, sort_keys=True).encode()).hexdigest()[:16]
    return {"num_leaves": len(rows), "digest": digest}


def hlo_collective_census(compiled_text: str) -> dict:
    """Post-partitioning collective instruction counts from compiled
    HLO text (the GSPMD engine's schedule lives here, not in the
    jaxpr)."""
    census: dict[str, int] = {}
    for m in _HLO_COLLECTIVE_RE.finditer(compiled_text):
        census[m.group(1)] = census.get(m.group(1), 0) + 1
    return census


def audit_program(step, args: Sequence[Any], *, engine: str = "custom",
                  donate_argnums: Sequence[int] = (),
                  alias_expected: Optional[Sequence[int]] = None,
                  mesh=None, hlo_census: bool = False) -> dict:
    """Audit one jitted program: trace, walk, prove. No execution.

    ``step`` is the jitted engine callable, ``args`` its example
    arguments (concrete arrays or ShapeDtypeStructs).  The schedule walk
    is trace-only; a donation proof or ``hlo_census`` pays one compile
    (donation realization only exists in the executable, and the
    post-SPMD collective census — the GSPMD engine's whole schedule —
    only exists there too).
    """
    import jax

    sched = extract_schedule(jax.make_jaxpr(step)(*args))
    findings = list(sched.findings)

    compiled_text = (step.lower(*args).compile().as_text()
                     if (donate_argnums or hlo_census) else None)
    donation = None
    if donate_argnums:
        donation = donation_proof(compiled_text, args, donate_argnums,
                                  alias_expected=alias_expected)
        findings.extend(donation["findings"])
        donation = {k: v for k, v in donation.items() if k != "findings"}

    census = None
    if hlo_census:
        census = hlo_collective_census(compiled_text)

    return {
        "engine": engine,
        "mesh_axes": ({str(k): int(v) for k, v in dict(mesh.shape).items()}
                      if mesh is not None else None),
        "schedule": [o.to_json() for o in sched.ops],
        "schedule_digest": schedule_digest(sched.ops),
        "comm_bytes_per_round": comm_bytes(sched.ops),
        "dynamic_comm": sched.has_dynamic,
        "donation": donation,
        "recompile_surface": _recompile_surface(args),
        "nondeterministic_ops": dict(sorted(sched.nondeterministic.items())),
        "hlo_collectives": census,
        "findings": [f.to_json() for f in findings],
    }


def audit_step_summary(step, args: Sequence[Any],
                       donate_argnums: Sequence[int] = (),
                       alias_expected: Optional[Sequence[int]] = None) -> dict:
    """The light manifest-sized audit of one live program: schedule
    digest + byte total + the two proof bits (run-manifest wiring)."""
    contract = audit_program(step, args, donate_argnums=donate_argnums,
                             alias_expected=alias_expected)
    return {
        "schedule_digest": contract["schedule_digest"],
        "collectives": len(contract["schedule"]),
        "comm_bytes_per_round": contract["comm_bytes_per_round"],
        "donation_ok": (contract["donation"]["ok"]
                        if contract["donation"] else None),
        "findings": len(contract["findings"]),
    }


# ---------------------------------------------------------------------------
# engine probes
# ---------------------------------------------------------------------------


def engine_audit_spec(cfg) -> dict:
    """The AUDIT_SPEC of the engine ``build_experiment(cfg)`` selects —
    the engines' read-only audit hook, so the loop/manifest wiring never
    hardcodes donation positions."""
    if cfg.fed.cohort_size > 0:
        from fedtpu.cohort import scheduler
        return scheduler.AUDIT_SPEC
    if cfg.fed.client_state == "stateless":
        from fedtpu.parallel import stateless
        return stateless.AUDIT_SPEC
    if cfg.fed.async_mode:
        from fedtpu.parallel import async_fed
        return async_fed.AUDIT_SPEC
    if cfg.run.model_parallel > 1:
        from fedtpu.parallel import tp
        return tp.AUDIT_SPEC
    if getattr(cfg.run, "mpmd", False):
        # The MPMD DAG's headline sub-program (the chain holds the round
        # math and the donated state); the per-sub-program specs live in
        # mpmd.AUDIT_SPECS and audit under the mpmd_* engine probes.
        from fedtpu.orchestration import mpmd
        return mpmd.AUDIT_SPEC
    from fedtpu.parallel import round as round_mod
    return round_mod.AUDIT_SPEC


def _synthetic_cfg(preset: str, synthetic_rows: int):
    import dataclasses as dc

    from fedtpu.config import get_preset

    cfg = get_preset(preset)
    # Same surgery as fedtpu check: the audit proves program structure,
    # not accuracy, and must run in seconds without the dataset.
    return dc.replace(cfg, data=dc.replace(
        cfg.data, csv_path=None, dataset_name=None,
        synthetic_rows=synthetic_rows))


def _probe_sync(cfg):
    from fedtpu.orchestration.loop import build_experiment
    from fedtpu.parallel import round as round_mod

    exp = build_experiment(cfg)
    return (exp.make_step(1), (exp.state, exp.batch),
            round_mod.AUDIT_SPEC, exp.mesh, True)


def _probe_async(cfg):
    import dataclasses as dc

    from fedtpu.orchestration.loop import build_experiment
    from fedtpu.parallel import async_fed

    # Derive the preset's FedBuff variant: the async engine owns
    # sampling/weighting/aggregation, so the sync-only knobs reset to
    # the values build_experiment requires (same composition matrix it
    # enforces loudly).
    cfg = dc.replace(
        cfg,
        fed=dc.replace(cfg.fed, async_mode=True, weighting="uniform",
                       participation_rate=1.0, server_opt="none",
                       dp_clip_norm=0.0, dp_noise_multiplier=0.0,
                       dp_adaptive_clip=False, robust_aggregation="none",
                       byzantine_clients=0, compress="none", scaffold=False,
                       personalize_steps=0, aggregation="psum"),
        run=dc.replace(cfg.run, model_parallel=1))
    exp = build_experiment(cfg)
    return (exp.make_step(1), (exp.state, exp.batch),
            async_fed.AUDIT_SPEC, exp.mesh, True)


def _probe_tp(cfg):
    import dataclasses as dc

    import jax

    from fedtpu.orchestration.loop import build_experiment
    from fedtpu.parallel import tp

    if jax.device_count() < 2 or jax.device_count() % 2:
        raise RuntimeError(
            f"tp probe needs an even device count >= 2 "
            f"(got {jax.device_count()}); rerun with --host-devices 8")
    cfg = dc.replace(
        cfg,
        fed=dc.replace(cfg.fed, participation_rate=1.0, aggregation="psum",
                       compress="none", robust_aggregation="none",
                       byzantine_clients=0, scaffold=False,
                       dp_adaptive_clip=False),
        run=dc.replace(cfg.run, model_parallel=2))
    exp = build_experiment(cfg)
    # GSPMD engine: the jaxpr is collective-free by design — the HLO
    # census below IS this engine's schedule contract.
    return (exp.make_step(1), (exp.state, exp.batch),
            tp.AUDIT_SPEC, exp.mesh, True)


def _probe_cohort(cfg):
    import dataclasses as dc

    import jax
    import jax.numpy as jnp

    from fedtpu.cohort import scheduler
    from fedtpu.data import load_dataset
    from fedtpu.data.sharding import pack_clients
    from fedtpu.models import build_model
    from fedtpu.ops import build_optimizer
    from fedtpu.parallel.mesh import make_mesh

    ds = load_dataset(cfg.data)
    model_cfg = cfg.model
    if model_cfg.kind == "mlp" and model_cfg.input_dim != ds.input_dim:
        model_cfg = dc.replace(model_cfg, input_dim=ds.input_dim)
    if model_cfg.num_classes != ds.num_classes:
        model_cfg = dc.replace(model_cfg, num_classes=ds.num_classes)
    init_fn, apply_fn = build_model(model_cfg)
    tx = build_optimizer(cfg.optim)
    k = cfg.shard.num_clients
    mesh = make_mesh(cfg.run.mesh_devices, k)
    step = scheduler.build_cohort_round_fn(
        mesh, apply_fn, tx, ds.num_classes, weighting=cfg.fed.weighting,
        cohorts_per_step=1, aggregation="psum",
        local_steps=cfg.fed.local_steps, prox_mu=cfg.fed.prox_mu)
    # Abstract example args: the contract is over shapes, so
    # ShapeDtypeStructs trace/lower identically to the scheduler's live
    # buffers without materializing a store.
    packed = pack_clients(ds.x_train, ds.y_train, cfg.shard)
    sds = jax.ShapeDtypeStruct
    stack = lambda tree, lead: jax.tree.map(
        lambda s: sds(tuple(lead) + tuple(s.shape), s.dtype), tree)
    p1 = jax.eval_shape(init_fn, jax.random.key(0))
    state = {"params": stack(p1, (k,)), "round": sds((), jnp.int32)}
    xs = {"opt": stack(jax.eval_shape(tx.init, p1), (1, k)),
          "x": sds((1,) + packed.x.shape, packed.x.dtype),
          "y": sds((1,) + packed.y.shape, packed.y.dtype),
          "mask": sds((1,) + packed.mask.shape, packed.mask.dtype)}
    return step, (state, xs), scheduler.AUDIT_SPEC, mesh, True


def _probe_mpmd(name: str):
    """One probe per MPMD sub-program (fedtpu.orchestration.mpmd): the
    DAG's collective schedules are gated INDEPENDENTLY — the client and
    metrics programs must stay collective-free, the aggregate/chain own
    the clients-axis reductions. Not part of AUDIT_ENGINES (the default
    golden set is pinned); audited via ``--engines mpmd_client,...``
    into their own goldens (tests/goldens/audit_mpmd_*.json)."""

    def probe(cfg):
        from fedtpu.orchestration import mpmd
        step, args, spec, mesh = mpmd.audit_probes(cfg)[name]
        return step, args, spec, mesh, True

    return probe


_PROBES = {
    "sync": _probe_sync,
    "async": _probe_async,
    "tp": _probe_tp,
    "cohort": _probe_cohort,
    "mpmd_client": _probe_mpmd("mpmd_client"),
    "mpmd_aggregate": _probe_mpmd("mpmd_aggregate"),
    "mpmd_chain": _probe_mpmd("mpmd_chain"),
    "mpmd_metrics": _probe_mpmd("mpmd_metrics"),
}


def audit_preset(preset: str = "income-8", *,
                 engines: Optional[Sequence[str]] = None,
                 synthetic_rows: int = 512) -> dict:
    """Audit every requested engine of one preset; returns the full
    JSON contract (the goldens' file format)."""
    import jax

    cfg = _synthetic_cfg(preset, synthetic_rows)
    wanted = tuple(engines) if engines else AUDIT_ENGINES
    unknown = set(wanted) - set(_PROBES)
    if unknown:
        raise ValueError(f"unknown audit engine(s) {sorted(unknown)}; "
                         f"available: {list(_PROBES)}")
    out_engines: dict[str, dict] = {}
    all_findings: list[dict] = []
    for name in wanted:
        try:
            step, args, spec, mesh, census = _PROBES[name](cfg)
        except (RuntimeError, ValueError) as exc:
            out_engines[name] = {"skipped": str(exc)}
            continue
        contract = audit_program(
            step, args, engine=spec["engine"],
            donate_argnums=spec["donate_argnums"],
            alias_expected=spec.get("alias_expected"), mesh=mesh,
            hlo_census=census)
        out_engines[name] = contract
        all_findings.extend(
            dict(f, engine=name) for f in contract["findings"])
    return {
        "version": AUDIT_VERSION,
        "preset": preset,
        "synthetic_rows": synthetic_rows,
        "device_count": jax.device_count(),
        "engines": out_engines,
        "findings": all_findings,
        "ok": not all_findings,
    }


# ---------------------------------------------------------------------------
# rendering / goldens
# ---------------------------------------------------------------------------


def render_audit_text(report: dict) -> str:
    lines = [f"audit: preset={report['preset']} "
             f"devices={report['device_count']} "
             f"rows={report['synthetic_rows']}"]
    for name, c in report["engines"].items():
        if "skipped" in c:
            lines.append(f"  [{name}] skipped: {c['skipped']}")
            continue
        mesh = c["mesh_axes"]
        lines.append(
            f"  [{name}] mesh={mesh} collectives={len(c['schedule'])} "
            f"digest={c['schedule_digest']} "
            f"comm={c['comm_bytes_per_round']}B/round"
            + (" (+dynamic)" if c["dynamic_comm"] else ""))
        for op in c["schedule"]:
            lines.append(
                f"    {op['op']}@{','.join(op['axes']) or '-'} "
                f"shapes={op['shapes']} x{op['trips']} "
                f"= {op['total_bytes']}B")
        if c["donation"] is not None:
            unal = [r for r in c["donation"]["table"] if not r["aliased"]]
            if not unal:
                tail = "all aliased"
            elif c["donation"]["ok"]:
                # Unaliased rows below the defect bar: donate-to-free
                # stream buffers or sub-floor leaves XLA declined.
                tail = (f"{len(unal)} unaliased "
                        f"({sum(r['bytes'] for r in unal)}B, benign)")
            else:
                tail = f"{len(unal)} UNALIASED"
            lines.append(
                f"    donation: {len(c['donation']['table'])} leaves, {tail}")
        if c["hlo_collectives"]:
            lines.append(f"    hlo collectives: {c['hlo_collectives']}")
        if c["nondeterministic_ops"]:
            lines.append(
                f"    nondeterministic ops: {c['nondeterministic_ops']}")
    if report["findings"]:
        lines.append("findings:")
        for f in report["findings"]:
            lines.append(f"  {f['code']} [{f['engine']}] {f['message']}")
    lines.append("ok" if report["ok"]
                 else f"{len(report['findings'])} finding(s)")
    return "\n".join(lines)


def _walk_diff(live: Any, golden: Any, path: str, out: list) -> None:
    if isinstance(golden, dict) and isinstance(live, dict):
        for key in sorted(set(golden) | set(live)):
            if key not in live:
                out.append(f"{path}.{key}: missing in live audit")
            elif key not in golden:
                out.append(f"{path}.{key}: not in golden (new field?)")
            else:
                _walk_diff(live[key], golden[key], f"{path}.{key}", out)
    elif isinstance(golden, list) and isinstance(live, list):
        if len(golden) != len(live):
            out.append(f"{path}: length {len(live)} != golden {len(golden)}")
        for i, (l, g) in enumerate(zip(live, golden)):
            _walk_diff(l, g, f"{path}[{i}]", out)
    elif live != golden:
        out.append(f"{path}: {live!r} != golden {golden!r}")


def _pinned(contract: Any) -> Any:
    """The part of one engine's contract a golden pins. The compiled
    census (``hlo_collectives``) counts the instructions XLA's combiner
    left, which moves with every compiler: where the traced schedule
    already pins the engine's collectives the census is reported only;
    where the schedule exists only in compiled text (the GSPMD engine:
    an empty traced schedule) the golden pins WHICH collectives appear,
    not how many."""
    if "hlo_collectives" not in contract:    # a skipped engine
        return contract
    pinned = {k: v for k, v in contract.items() if k != "hlo_collectives"}
    if not contract.get("schedule"):
        pinned["hlo_collectives"] = dict.fromkeys(
            contract["hlo_collectives"] or {}, "present")
    return pinned


def diff_audit(live: dict, golden: dict) -> list[str]:
    """Human-readable mismatch list between a live audit report and a
    committed golden contract; empty means the contract holds."""
    out: list[str] = []
    sides = [{**r, "engines": {name: _pinned(c) for name, c
                               in r.get("engines", {}).items()}}
             for r in (live, golden)]
    _walk_diff(*sides, "audit", out)
    return out
