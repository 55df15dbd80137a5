"""Host-side tabular data pipeline.

Re-implements (once, as a library) the preamble duplicated across all three
reference scripts: CSV load -> LabelEncoder over every object column ->
StandardScaler -> ``train_test_split(test_size=0.2, random_state=42)``
(FL_CustomMLPCLassifierImplementation_Multiple_Rounds.py:216-246,
FL_SkLearn_MLPClassifier_Limitation.py:163-197).

Differences from the reference, by design:
  * The reference makes EVERY MPI rank read and preprocess the whole CSV and
    then broadcasts rank 0's split over it anyway (SURVEY.md §3.1). fedtpu is
    single-controller: the host loads once and shards straight onto the device
    mesh — there is no broadcast step to replicate.
  * The reference fits its scaler on the full dataset before splitting
    (FL_CustomMLP...:235-236), leaking test statistics into train. That is the
    parity default here (``scaler_leakage_parity=True``) but the clean
    fit-on-train-only path is one flag away.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from fedtpu.config import DataConfig


@dataclasses.dataclass
class Dataset:
    """A preprocessed train/test split, still on host as float32/int32 numpy."""

    x_train: np.ndarray
    y_train: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    num_classes: int
    feature_names: tuple
    label_classes: np.ndarray  # original label values, sorted (LabelEncoder order)
    # Where the rows came from: {"kind": "csv", "path", "parser", "rows"} or
    # {"kind": "synthetic", "generator", "rows"}. The run loop logs it first
    # and records it in the manifest, so a synthetic stand-in can never pass
    # for the dataset a preset is named after.
    source: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # Whose each training row is, where the data are born partitioned (a
    # federated corpus, fedtpu.data.tokens): pack_clients then keeps the
    # clients' own rows and ShardConfig's strategy carves nothing.
    client_of_row: Optional[np.ndarray] = None

    @property
    def input_dim(self) -> int:
        return self.x_train.shape[1]


def _label_encode(df: "pd.DataFrame") -> Dict[str, np.ndarray]:
    """Encode every object column to sorted-unique integer codes.

    Equivalent to the reference's per-column ``LabelEncoder().fit_transform``
    (FL_CustomMLP...:222-230): sklearn's LabelEncoder maps values to indices
    into ``np.unique(values)``, which is exactly pandas factorize with sorting.
    """
    import pandas as pd

    encoders = {}
    for col in df.columns:
        # The reference selects ``object`` dtype columns (:224); pandas 3
        # loads text as Arrow-backed string dtype, so check both.
        if df[col].dtype == object or pd.api.types.is_string_dtype(df[col]):
            classes, codes = np.unique(df[col].to_numpy(), return_inverse=True)
            df[col] = codes
            encoders[col] = classes
    return encoders


def _standard_scale(x: np.ndarray, with_mean: bool,
                    stats_from: Optional[np.ndarray] = None):
    """StandardScaler semantics: (x - mean) / std with ddof=0; std==0 -> 1.

    ``with_mean=False`` matches FL_SkLearn...:184 (divide by std only).
    """
    src = x if stats_from is None else stats_from
    mean = src.mean(axis=0) if with_mean else np.zeros(src.shape[1], src.dtype)
    std = src.std(axis=0)
    std = np.where(std == 0.0, 1.0, std)
    return (x - mean) / std, (mean, std)


def _train_test_split(x, y, test_size: float, seed: int):
    """Bit-parity with sklearn's ``train_test_split(random_state=seed)``:
    a seeded permutation with the last ``ceil(n*test_size)`` indices as test
    (sklearn draws ``permutation(n)``, takes the first n_test as test)."""
    from sklearn.model_selection import train_test_split  # parity source of truth

    return train_test_split(x, y, test_size=test_size, random_state=seed)


def _load_encoded(csv_path: str, use_native: bool):
    """Load + label-encode a CSV: ``(column_names, float64 matrix, classes,
    parser)`` where object columns in the matrix already hold sorted-unique
    codes and ``parser`` names who parsed it ('native' | 'pandas').

    Primary path is the native C++ loader (fedtpu.native — one parse pass,
    the host-runtime replacement for the reference's per-rank pandas +
    LabelEncoder preamble, FL_CustomMLP...:216-230); pandas is the fallback
    when no toolchain is available. A parity test pins both to identical
    output on the shipped income CSV; see csv_loader.cpp for the known
    inference divergences on exotic inputs (pandas NA tokens)."""
    if use_native:
        from fedtpu import native
        if native.available():
            header, _, mat, classes = native.load_csv(csv_path)
            return list(header), mat, classes, "native"
    # imported where a CSV goes this way: a second of every process's start
    import pandas as pd

    df = pd.read_csv(csv_path)
    encoders = _label_encode(df)
    return list(df.columns), df.to_numpy(dtype=np.float64), encoders, "pandas"


def synthetic_income_like(rows: int, features: int, classes: int,
                          seed: int = 7):
    """A balanced, linearly-separable-ish stand-in for
    balanced_income_data.csv, for tests and environments without the CSV."""
    rng = np.random.default_rng(seed)
    y = np.arange(rows) % classes
    rng.shuffle(y)
    centers = rng.normal(0.0, 2.0, size=(classes, features))
    x = centers[y] + rng.normal(0.0, 1.0, size=(rows, features))
    return x.astype(np.float32), y.astype(np.int32)


def load_tabular_dataset(cfg: DataConfig) -> Dataset:
    """Load + preprocess per the reference pipeline; see module docstring."""
    if cfg.csv_path is None:
        x, y = synthetic_income_like(cfg.synthetic_rows, cfg.synthetic_features,
                                     cfg.synthetic_classes)
        label_classes = np.arange(cfg.synthetic_classes)
        feature_names = tuple(f"f{i}" for i in range(x.shape[1]))
        source = {"kind": "synthetic", "generator": "synthetic_income_like",
                  "rows": int(len(x))}
    else:
        columns, mat, encoders, parser = _load_encoded(cfg.csv_path,
                                                       cfg.native_loader)
        source = {"kind": "csv", "path": cfg.csv_path, "parser": parser,
                  "rows": int(len(mat))}
        if cfg.label_column not in columns:
            # Same guard as FL_CustomMLP...:219-220.
            raise KeyError(
                f"'{cfg.label_column}' not found in dataset columns. "
                f"Available columns: {list(columns)}")
        li = columns.index(cfg.label_column)
        y = mat[:, li]
        x = np.delete(mat, li, axis=1)
        # Re-encode labels to contiguous 0..K-1 class indices regardless of
        # source dtype: numeric label columns (e.g. the diabetes 'Outcome'
        # path, FL_CustomMLP...:217) bypass _label_encode, and raw values like
        # {1, 2} would otherwise be used as class indices directly —
        # silently clamping in the loss and falling off the confusion matrix.
        original_classes, y = np.unique(y, return_inverse=True)
        label_classes = encoders.get(cfg.label_column, original_classes)
        feature_names = tuple(c for c in columns if c != cfg.label_column)

    num_classes = int(len(np.unique(y)))

    if cfg.scaler_leakage_parity:
        # Reference behavior: scale on the full data, then split
        # (FL_CustomMLP...:235-239).
        x, _ = _standard_scale(x, cfg.scale_with_mean)
        x_train, x_test, y_train, y_test = _train_test_split(
            x, y, cfg.test_size, cfg.split_seed)
    else:
        x_train, x_test, y_train, y_test = _train_test_split(
            x, y, cfg.test_size, cfg.split_seed)
        x_train, (mean, std) = _standard_scale(x_train, cfg.scale_with_mean)
        x_test = (x_test - (mean if cfg.scale_with_mean else 0.0)) / std

    return Dataset(
        x_train=np.asarray(x_train, dtype=np.float32),
        y_train=np.asarray(y_train, dtype=np.int32),
        x_test=np.asarray(x_test, dtype=np.float32),
        y_test=np.asarray(y_test, dtype=np.int32),
        num_classes=num_classes,
        feature_names=feature_names,
        label_classes=np.asarray(label_classes),
        source=source,
    )
