"""The round on one clock (PR 23): phase spans of the host loop in the sink,
the same phases as ``fedtpu.<phase>`` annotations in a profiler trace, the
stage scopes of the round program and the ``program_scopes`` event that
joins them to a trace's operations, and ``run_experiment``'s ``on_chunk``.
"""

import dataclasses
import glob
import json
import os

import jax
import numpy as np
import pytest

from fedtpu.analysis.program import program_scopes
from fedtpu.config import (DataConfig, ExperimentConfig, FedConfig,
                           ModelConfig, RunConfig, ShardConfig,
                           TelemetryConfig)
from fedtpu.orchestration.loop import (STATE_CHECK, _emit_program_scopes,
                                       _tree_finite, build_experiment,
                                       run_experiment)
from fedtpu.parallel.round import STAGES

PHASES = ("dispatch", "chunk_fetch", "stop_check", "state_check")


def _cfg(rounds, events=None, **run_kw):
    run_kw.setdefault("log_every", 1000)
    if events is not None:
        run_kw["telemetry"] = TelemetryConfig(events_path=str(events))
    return ExperimentConfig(
        data=DataConfig(csv_path=None, synthetic_rows=512),
        shard=ShardConfig(num_clients=8),
        fed=FedConfig(rounds=rounds, termination_patience=1000),
        run=RunConfig(**run_kw))


def _events(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh]


# ------------------------------------------------------------ the join
HLO = """HloModule jit_round_step, entry_computation_layout={()->f32[8]{0}}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %tanh.9 = f32[8]{0} tanh(%p), metadata={op_name="jit(f)/client_train/tanh"}
}

%body (c: (f32[8], s32[])) -> (f32[8], s32[]) {
  %c = (f32[8]{0}, s32[]) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%c), index=0
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%gte.1)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  %fusion.2 = f32[8]{0} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/client_train/vmap(tanh)"}
  %all-reduce.3 = f32[8]{0} all-reduce(%fusion.2), to_apply=%add, metadata={op_name="jit(f)/while/body/aggregate/psum"}
  %copy.4 = f32[8]{0} copy(%fusion.2)
  %fusion.5 = f32[8]{0} fusion(%copy.4, %all-reduce.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/transpose(jvp(client_eval))/mul"}
  %gte.2 = s32[] get-tuple-element(%c), index=1
  ROOT %tuple.6 = (f32[8]{0}, s32[]) tuple(%fusion.5, %gte.2)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %copy.7 = f32[8]{0} copy(%x)
  %t = (f32[8]{0}, s32[]) tuple(%copy.7, %zero)
  %while.8 = (f32[8]{0}, s32[]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(f)/while"}
  %gte.3 = f32[8]{0} get-tuple-element(%while.8), index=0
  ROOT %fusion.10 = f32[8]{0} fusion(%gte.3), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/metrics/div"}
}
"""


def test_program_scopes_reads_the_stage_of_each_operation():
    got = program_scopes(HLO, STAGES)
    assert got["scopes"] == {
        # own op_name: the innermost stage, through a transform's wrapper
        "fusion.2 f32[8]": "client_train",
        "all-reduce.3 f32[8]": "aggregate",
        "fusion.5 f32[8]": "client_eval",
        "fusion.10 f32[8]": "metrics",
        # made by the compiler: the stage its users agree on, through a chain
        "copy-start.1 f32[8]": "client_train",
        "copy-done.1 f32[8]": "client_train",
        "copy.4 f32[8]": "client_eval",
    }
    # nothing is inherited across the loop or a tuple; the instruction
    # inside the fusion body is no operation of the trace
    assert got["unscoped"] == ["copy.7 f32[8]", "while.8 f32[8]"]


def _mlp():
    return _cfg(2)


def _convnet():
    return ExperimentConfig(
        data=DataConfig(dataset_name="cifar10", synthetic_rows=64),
        shard=ShardConfig(num_clients=8),
        model=ModelConfig(kind="convnet", num_classes=10, hidden_sizes=(32,),
                          compute_dtype="bfloat16"),
        fed=FedConfig(rounds=2))


@pytest.mark.parametrize("make,width", [(_mlp, 1), (_mlp, 4), (_convnet, 1)])
def test_round_program_names_its_stages(make, width):
    exp = build_experiment(make())
    text = exp.make_step(width).lower(exp.state, exp.batch).compile().as_text()
    for stage in ("client_train", "client_eval", "aggregate", "metrics"):
        assert f"/{stage}/" in text, stage
    got = program_scopes(text, STAGES)
    assert set(got["scopes"].values()) == {"client_train", "client_eval",
                                           "aggregate", "metrics"}
    heavy = ("fusion", "convolution", "dot", "all-reduce", "all-gather",
             "reduce-scatter", "collective-permute", "all-to-all")

    def is_heavy(key):
        name = key.split(" ")[0]
        return "fusion" in name or name.startswith(heavy)
    mapped = sum(map(is_heavy, got["scopes"]))
    missed = sum(map(is_heavy, got["unscoped"]))
    assert mapped >= 20 and mapped / (mapped + missed) >= 0.9


# ---------------------------------------------------------- the sink
@pytest.mark.parametrize("rounds,run_kw", [
    (5, {}),
    (10, {"rounds_per_step": 4}),
    (8, {"rounds_per_step": 2, "pipelined_stop": True}),
], ids=["width1", "width4", "pipelined"])
def test_every_chunk_has_its_phase_spans(tmp_path, rounds, run_kw):
    path = tmp_path / "ev.jsonl"
    run_experiment(_cfg(rounds, path, **run_kw), verbose=False)
    events = _events(path)
    spans = [e for e in events if e["kind"] == "span"]
    laps = {e["round"]: e for e in spans if e["phase"] == "chunk"}
    width = run_kw.get("rounds_per_step", 1)
    ends = [min(r + width, rounds) for r in range(0, rounds, width)]
    assert sorted(laps) == ends
    pipelined = bool(run_kw.get("pipelined_stop"))
    for last in ends[1:]:
        take = laps[last]["payload"]["rounds"]
        mine = {e["phase"]: e for e in spans
                if e["round"] == last and e["phase"] in PHASES}
        # under pipelined_stop the state is checked at boundaries only:
        # here once, at the loop's exit, labelled with the last round
        want = set(PHASES)
        if pipelined:
            want.discard("state_check")
            assert mine.pop("state_check", None) is None or last == ends[-1]
        # a width the loop meets for the first time is a compile span
        first = any(e["phase"] == "compile" and e["round"] == last
                    for e in spans)
        assert set(mine) == want - ({"dispatch"} if first else set()), last
        assert all(e["payload"]["rounds"] == take for e in mine.values())
        if not pipelined:
            # the lap runs from the previous chunk's fetch to this one's:
            # the previous chunk's two checks, this chunk's dispatch and
            # fetch, and whatever of the loop no phase covers
            lap = laps[last]
            order = sorted((e for e in spans
                            if e["phase"] in PHASES + ("compile",)),
                           key=lambda e: e["t_start"])
            fetches = [i for i, e in enumerate(order)
                       if e["phase"] == "chunk_fetch"]
            at = next(i for i in fetches if order[i]["round"] == last)
            inside = order[fetches[fetches.index(at) - 1] + 1:at + 1]
            assert [(e["phase"], e["round"]) for e in inside] == [
                ("stop_check", last - take), ("state_check", last - take),
                ("compile" if first else "dispatch", last),
                ("chunk_fetch", last)] or take != width
            assert sum(e["dur_s"] for e in inside) <= lap["dur_s"] + 2e-3
    # the host runs one phase at a time
    seq = sorted((e for e in spans if e["phase"] in PHASES + ("compile",)),
                 key=lambda e: e["t_start"])
    for a, b in zip(seq, seq[1:]):
        assert a["t_start"] + a["dur_s"] <= b["t_start"] + 2e-3, (a, b)
    if pipelined:
        assert [e["phase"] for e in spans
                if e["phase"] == "state_check"] == ["state_check"]
        # a chunk's fetch follows the next chunk's dispatch
        by = {(e["phase"], e["round"]): e["t_start"] for e in spans}
        assert by[("dispatch", ends[2])] < by[("chunk_fetch", ends[1])]
    kinds = [(e["kind"], e["phase"]) for e in events if e["kind"] != "log"]
    assert kinds.count(("span", "epilogue")) == 1
    assert kinds[-2:] == [("span", "epilogue"), ("run_end", None)]
    assert not any(e["kind"] == "program_scopes" for e in events)


def test_on_chunk_is_called_when_a_chunks_metrics_are_on_the_host(tmp_path):
    path = tmp_path / "ev.jsonl"
    seen = []

    def on_chunk(last_round, take):
        # the chunk span of this chunk is already in the sink
        seen.append((last_round, take, sum(
            1 for e in _events(path) if e["phase"] == "chunk")))
    result = run_experiment(_cfg(10, path, rounds_per_step=4), verbose=False,
                            on_chunk=on_chunk)
    assert seen == [(4, 4, 1), (8, 4, 2), (10, 2, 3)]
    assert result.rounds_run == 10


# ------------------------------------------------------- the profiler
class _Counting:
    """Counts constructions of a profiler annotation class."""

    def __init__(self, monkeypatch, name):
        self.made = []
        real = getattr(jax.profiler, name)
        made = self.made

        class Counted(real):
            def __init__(self, *args, **kwargs):
                made.append((args, kwargs))
                super().__init__(*args, **kwargs)
        monkeypatch.setattr(jax.profiler, name, Counted)


def test_annotations_land_in_the_trace_without_the_sink(tmp_path, monkeypatch):
    spans = _Counting(monkeypatch, "TraceAnnotation")
    steps = _Counting(monkeypatch, "StepTraceAnnotation")
    prof = tmp_path / "prof"
    run_experiment(_cfg(6, profile_dir=str(prof), profile_rounds=3),
                   verbose=False)
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(prof / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = [[(e.name, dict(e.stats)) for e in line.events
              if e.name.startswith("fedtpu.")]
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines]
    (mine,) = [line for line in lines if line]      # one thread: the loop's
    rounds = {name: [stats.get("round", stats.get("step_num"))
                     for n, stats in mine if n == name]
              for name in {n for n, _ in mine}}
    # the window opens after round 1's fetch and closes after round 4's
    assert rounds["fedtpu.dispatch"] == [2, 3, 4]
    assert rounds["fedtpu.chunk_fetch"] == [2, 3, 4]
    assert rounds["fedtpu.chunk"] == [2, 3, 4]
    assert rounds["fedtpu.stop_check"] == [1, 2, 3]
    assert rounds["fedtpu.state_check"] == [1, 2, 3]
    assert len(steps.made) == 3 and len(spans.made) == 12


def test_a_dropped_chunk_closes_its_step_annotation(tmp_path, monkeypatch):
    """Under ``pipelined_stop`` an early stop drops the chunk in flight
    unprocessed: its ``fedtpu.chunk`` step annotation is closed all the
    same, before the trace is finalised."""
    exits = []
    real = jax.profiler.StepTraceAnnotation

    class Counted(real):
        def __exit__(self, *exc):
            exits.append(self)
            return super().__exit__(*exc)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Counted)
    steps = _Counting(monkeypatch, "StepTraceAnnotation")
    cfg = _cfg(30, profile_dir=str(tmp_path / "prof"), profile_rounds=30,
               rounds_per_step=2, pipelined_stop=True)
    cfg = cfg.replace(fed=dataclasses.replace(
        cfg.fed, tolerance=1.0, termination_patience=4))
    result = run_experiment(cfg, verbose=False)
    assert result.stopped_early and result.rounds_run < 30
    # the overshoot chunk was dispatched inside the window and never fetched
    assert len(steps.made) >= 2 and len(exits) == len(steps.made)
    assert len(set(map(id, exits))) == len(exits)


def test_no_profiler_object_is_made_with_sink_and_profiler_off(monkeypatch,
                                                               capsys):
    spans = _Counting(monkeypatch, "TraceAnnotation")
    steps = _Counting(monkeypatch, "StepTraceAnnotation")
    run_experiment(_cfg(3), verbose=False)
    assert spans.made == [] and steps.made == []
    assert capsys.readouterr().out == ""


def test_tracing_changes_no_result_and_says_which_operation_is_whose(tmp_path):
    plain = run_experiment(_cfg(5), verbose=False)
    path = tmp_path / "ev.jsonl"
    traced = run_experiment(
        _cfg(5, path, profile_dir=str(tmp_path / "prof"), profile_rounds=2),
        verbose=False)
    for a, b in zip(jax.tree.leaves(plain.final_params),
                    jax.tree.leaves(traced.final_params)):
        np.testing.assert_array_equal(a, b)
    assert plain.global_metrics == traced.global_metrics
    np.testing.assert_array_equal(np.stack(plain.loss), np.stack(traced.loss))
    # one event a program, only where sink and profile are both on
    said = {e["payload"]["program"]: e["payload"] for e in _events(path)
            if e["kind"] == "program_scopes"}
    assert sorted(said) == ["round_step", "state_check"]
    assert said["round_step"]["width"] == 1
    assert set(said["round_step"]["scopes"].values()) == set(STAGES)
    assert set(said["state_check"]["scopes"].values()) == {STATE_CHECK}
    assert not any("stale_metadata" in p for p in said.values())
    # the four maps of one walk: this program names no layer and no piece,
    # and every operation has a direction (no update scope here: the
    # resident engine's optimizer step is part of its training pass)
    for payload in said.values():
        assert payload["layers"] == {} and payload["pieces"] == {}
        assert set(payload["passes"]) == {*payload["scopes"],
                                          *payload["unscoped"]}
    assert set(said["round_step"]["passes"].values()) == {"forward",
                                                          "backward"}
    # what the join cost is on the event's own clock
    took = [e["dur_s"] for e in _events(path) if e["kind"] == "program_scopes"]
    assert len(took) == 2 and all(0.0 < d < 30.0 for d in took)


def test_an_executable_that_names_no_stage_is_said_to_be_stale():
    """A persistent-cache hit serves the executable of whichever checkout
    compiled it first, metadata included (JAX's cache key leaves metadata
    out): a program text that names no stage is flagged, not listed as a
    program whose every operation is unscoped."""
    said = []

    class Sink:
        def event(self, kind, **payload):
            said.append((kind, payload))
    x = jax.numpy.arange(8.0)
    _emit_program_scopes(Sink(), "round_step", 1, jax.jit(lambda v: v * 2), x)
    _emit_program_scopes(Sink(), STATE_CHECK, None, _tree_finite, x)
    (_, stale), (_, fresh) = said
    assert stale["stale_metadata"] is True and not stale["scopes"]
    assert "stale_metadata" not in fresh and fresh["scopes"]


# --------------------------------------------- the persistent cache's key
_PROBE = """
import sys
import numpy as np
import jax
import jax.numpy as jnp
from jax import monitoring
from fedtpu.compilation import configure_persistent_cache
hits = []
monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
configure_persistent_cache()

@jax.jit
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.tanh(x @ x).sum()

def another_caller(x):
    return f(x)

(another_caller if sys.argv[2:] else f)(
    np.ones((32, 32), np.float32)).block_until_ready()
print("HITS", len(hits))
# what keeps the callers out of the key keeps the scopes in the program
x = np.ones((32, 32), np.float32)
assert f"/{sys.argv[1]}/" in f.lower(x).compile().as_text()
"""


@pytest.mark.parametrize("second,served", [
    (("a", "other_scope"), False), (("b", "one_scope"), True),
    (("a", "one_scope", "traced from another caller"), True)],
    ids=["another_scope_is_not_served", "another_path_is_served",
         "another_caller_is_served"])
def test_a_cached_executable_is_its_own_checkouts(tmp_path, second, served):
    """``configure_persistent_cache`` keys an entry by the metadata too,
    names files from the checkout's root and leaves the stack of callers out
    of a location: a function compiled under one scope and again under
    another is not served the first one's executable (whose ``op_name``s are
    the first one's); the same source at another path is, and so is the
    same function traced from another caller (every job of a process builds
    its programs anew, each from its own call path). Two checkouts: the
    package linked under two roots, the traced function in a file of each."""
    import subprocess
    import sys
    import fedtpu
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX", None)

    def hits(root, *args):
        root = tmp_path / root
        if not root.exists():
            root.mkdir()
            os.symlink(os.path.dirname(fedtpu.__file__), root / "fedtpu")
            (root / "probe.py").write_text(_PROBE)
        done = subprocess.run([sys.executable, "probe.py", *args], cwd=root,
                              env=env, capture_output=True, text=True,
                              timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        return int(done.stdout.strip().splitlines()[-1].split()[1])

    assert hits("a", "one_scope") == 0           # cold: the entry is written
    assert hits("a", "one_scope") == 1           # and found again
    assert hits(*second) == (1 if served else 0)
