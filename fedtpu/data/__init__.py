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
    if cfg.dataset_name == "tokens":
        # the model's vocabulary, sequence length and the client count are
        # not DataConfig's to know: build_experiment's caller hands the
        # corpus in, or load_token_corpus builds it from the whole config
        raise ValueError("dataset_name='tokens' is built from the whole "
                         "experiment config: fedtpu.data.load_token_corpus")
    if cfg.dataset_name is not None:
        raise ValueError(f"unknown dataset_name: {cfg.dataset_name!r}")
    return load_tabular_dataset(cfg)


def load_token_corpus(cfg) -> Dataset:
    """``DataConfig.dataset_name='tokens'`` from a whole ExperimentConfig:
    ``data.synthetic_rows`` packed sequences in all, ``data.
    synthetic_features`` tokens each, over ``shard.num_clients`` clients, in
    the model's vocabulary, seeded by ``data.split_seed``."""
    from fedtpu.data.tokens import synthetic_token_corpus
    return synthetic_token_corpus(
        cfg.shard.num_clients, cfg.data.synthetic_rows,
        cfg.data.synthetic_features, cfg.model.vocab_size,
        seed=cfg.data.split_seed, test_size=cfg.data.test_size)


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
