"""fedtpu — a TPU-native federated-learning framework.

A from-scratch JAX/XLA re-design of the capabilities of
``i-HamidZafar/Federated-Learning-with-MPI`` (multi-round weighted FedAvg over
per-client MLP training, sklearn warm-start parity, federated hyperparameter
grid search). The reference runs one MPI process per federated client and moves
model weights through rank-0 with pickled ``comm.gather``/``comm.bcast``
(FL_CustomMLPCLassifierImplementation_Multiple_Rounds.py:101-120); fedtpu runs
one client per TPU-core shard of a ``('clients',)`` ``jax.sharding.Mesh`` and
aggregates with ``jax.lax.psum`` over ICI inside a single jit-compiled round —
weights never leave device memory.

Public API (stable):
    fedtpu.config      — typed configs + the BASELINE.json presets
    fedtpu.data        — CSV pipeline, client sharding (IID / non-IID), packing
    fedtpu.models      — pure-pytree MLP and ConvNet
    fedtpu.ops         — losses, in-graph classification metrics, optimizers
    fedtpu.parallel    — mesh helpers, the shard_map federated round
    fedtpu.orchestration — host round loop, early stopping, checkpointing
    fedtpu.sweep       — federated hyperparameter grid search
    fedtpu.parity      — sklearn MLPClassifier warm-start comparison path
    fedtpu.telemetry   — tracing, metrics, run manifests, `fedtpu report`
"""

__version__ = "0.1.0"

from fedtpu.config import (  # noqa: F401
    DataConfig,
    ShardConfig,
    ModelConfig,
    OptimConfig,
    FedConfig,
    RunConfig,
    TelemetryConfig,
    ExperimentConfig,
    PRESETS,
    get_preset,
)

_LAZY = {
    # Heavyweight entry points resolved on first access (PEP 562) so a bare
    # ``import fedtpu`` doesn't pull jax/pandas/orbax/sklearn.
    "run_experiment": ("fedtpu.orchestration.loop", "run_experiment"),
    "build_experiment": ("fedtpu.orchestration.loop", "build_experiment"),
    "run_grid_search": ("fedtpu.sweep.grid", "run_grid_search"),
    "run_parity_demo": ("fedtpu.parity.sklearn_warmstart", "run_parity_demo"),
    "make_mesh": ("fedtpu.parallel.mesh", "make_mesh"),
    "client_sharding": ("fedtpu.parallel.mesh", "client_sharding"),
    "build_round_fn": ("fedtpu.parallel.round", "build_round_fn"),
    "init_federated_state": ("fedtpu.parallel.round", "init_federated_state"),
    "make_server_optimizer": ("fedtpu.ops.server_opt",
                              "make_server_optimizer"),
    "build_personalize_fn": ("fedtpu.training.personalize",
                             "build_personalize_fn"),
    # Sweep-winner artifact (the reference only prints its winner,
    # hyperparameters_tuning.py:130-132).
    "save_best_weights": ("fedtpu.sweep.grid", "save_best_weights"),
    "load_best_weights": ("fedtpu.sweep.grid", "load_best_weights"),
    # Telemetry (docs/observability.md). The package itself is
    # import-light (stdlib only) but stays lazy for symmetry.
    "make_tracer": ("fedtpu.telemetry.trace", "make_tracer"),
    "default_registry": ("fedtpu.telemetry.metrics", "default_registry"),
    "build_manifest": ("fedtpu.telemetry.manifest", "build_manifest"),
    "TelemetryLogger": ("fedtpu.telemetry.log", "TelemetryLogger"),
    "render_report": ("fedtpu.telemetry.report", "render_report"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        module, attr = _LAZY[name]
        value = getattr(importlib.import_module(module), attr)
        globals()[name] = value          # cache: next access skips __getattr__
        return value
    raise AttributeError(f"module 'fedtpu' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
