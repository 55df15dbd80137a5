from fedtpu.data.tabular import load_tabular_dataset, Dataset  # noqa: F401
from fedtpu.data.sharding import (  # noqa: F401
    shard_indices,
    pack_clients,
    ClientBatch,
)


def load_dataset(cfg) -> Dataset:
    """Single dispatch point for ``DataConfig.dataset_name`` — every consumer
    (run/sweep/parity) resolves data through here so named datasets like
    'cifar10' are honored everywhere, not just in ``build_experiment``."""
    if cfg.dataset_name == "cifar10":
        from fedtpu.data.cifar10 import load_cifar10
        return load_cifar10(synthetic_rows=cfg.synthetic_rows)
    if cfg.dataset_name is not None:
        raise ValueError(f"unknown dataset_name: {cfg.dataset_name!r}")
    return load_tabular_dataset(cfg)


def data_notice(ds: Dataset) -> str:
    """One log line saying which rows a run trains on — the run loops'
    first line. A preset named after a dataset falls back to a synthetic
    stand-in when its file is absent, and that must be said, not
    inferred."""
    src = ds.source
    if src.get("kind") == "synthetic":
        return (f"Data: SYNTHETIC stand-in ({src['generator']}, "
                f"{src['rows']} rows, {ds.input_dim} features) — no dataset "
                "file was given or found; pass --csv PATH to train on real "
                "rows.")
    if not src:
        return (f"Data: caller-supplied dataset ({len(ds.x_train)} train "
                f"rows, {ds.input_dim} features).")
    parser = f", {src['parser']} parser" if "parser" in src else ""
    return (f"Data: {src['kind']} {src['path']} ({src['rows']} rows, "
            f"{ds.input_dim} features{parser}).")
