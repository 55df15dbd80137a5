"""Seeded data generators of the benchmark: the same ``--seed`` gives the
same rows. Copies of the program's stand-ins (``chip_smoke.write_income_csv``
and ``fedtpu.data.cifar10.synthetic_cifar_like``; originals listed in PERF.md
for a later PR to retire), vectorised so that set-up stays short at the
sizes a chip holds. Each returns the program's input type, a
``fedtpu.data.tabular.Dataset``, with ``source`` naming the generator.
"""

from __future__ import annotations

import numpy as np

# Levels per categorical column of balanced_income_data.csv's shape (the
# reference label-encodes every string column to sorted-unique codes; the
# generator draws the codes directly, which is the same distribution).
INCOME_CATEGORICAL = {"workclass": 6, "education": 8, "marital.status": 5,
                      "occupation": 8, "relationship": 5, "race": 5,
                      "sex": 2, "native.country": 6}
INCOME_NUMERIC = ("age", "fnlwgt", "education.num", "capital.gain",
                  "capital.loss", "hours.per.week")


def _z(v):
    v = v.astype(np.float64)
    return (v - v.mean()) / v.std()


def income_like(rows: int, seed: int):
    """``rows`` of the reference CSV's shape (6 integer columns, 8 encoded
    string columns, a balanced binary label). The label is the sign of a
    noisy linear score cut at the median, so the reference's MLP stays a few
    points short of perfect and its metrics keep moving round to round.
    Returns ``(x float64 (rows, 14), y int32 (rows,), feature_names)``."""
    rng = np.random.default_rng(seed)
    num = {
        "age": rng.integers(17, 91, rows),
        "fnlwgt": rng.integers(12_000, 1_500_000, rows),
        "education.num": rng.integers(1, 17, rows),
        "capital.gain": np.where(rng.random(rows) < 0.1,
                                 rng.integers(100, 100_000, rows), 0),
        "capital.loss": np.where(rng.random(rows) < 0.05,
                                 rng.integers(100, 4_500, rows), 0),
        "hours.per.week": rng.integers(1, 100, rows),
    }
    cat = {name: rng.integers(0, levels, rows)
           for name, levels in INCOME_CATEGORICAL.items()}
    score = (1.2 * _z(num["age"]) + 1.5 * _z(num["education.num"])
             + 0.8 * _z(num["hours.per.week"])
             + 1.0 * _z(np.log1p(num["capital.gain"]))
             + 0.6 * _z(cat["education"]) + 0.5 * _z(cat["occupation"])
             + 0.5 * _z(cat["sex"]) - 0.4 * _z(cat["marital.status"])
             + rng.normal(0.0, 1.4, rows))
    y = np.zeros(rows, np.int32)
    y[np.argsort(score, kind="stable")[rows // 2:]] = 1
    names = INCOME_NUMERIC + tuple(INCOME_CATEGORICAL)
    cols = {**num, **cat}
    x = np.stack([cols[n].astype(np.float64) for n in names], axis=1)
    return x, y, names


def cifar_like(rows: int, seed: int, image_shape=(32, 32, 3), classes=10,
               center_scale=0.12, noise_std=0.5, label_noise=0.15):
    """Class-conditioned Gaussian blobs with label noise, CIFAR-shaped and
    not separable (the program's calibration: plateau near 0.81). Drawn in
    float32 in one pass: 62,500 images are 768 MB, and a float64 draw would
    double set-up for nothing. Returns ``(x float32 (rows, 3072), y int32)``."""
    rng = np.random.default_rng(seed)
    y = (np.arange(rows) % classes).astype(np.int32)
    rng.shuffle(y)
    dim = int(np.prod(image_shape))
    centers = rng.normal(0.0, center_scale, (classes, dim)).astype(np.float32)
    x = rng.standard_normal((rows, dim), dtype=np.float32)
    x *= np.float32(noise_std)
    x += centers[y]
    y_obs = y.copy()
    flip = rng.random(rows) < label_noise
    y_obs[flip] = rng.integers(0, classes, int(flip.sum()))
    return x, y_obs


def _split(x, y, test_size: float, seed: int):
    """A seeded permutation; the last ``ceil(n * test_size)`` rows are held
    out (the reference's 80/20 split, by the benchmark's own seed)."""
    n = len(x)
    n_test = int(np.ceil(n * test_size))
    perm = np.random.default_rng(seed + 1).permutation(n)
    tr, te = perm[:n - n_test], perm[n - n_test:]
    return x[tr], y[tr], x[te], y[te]


def make(spec: dict, num_clients: int, seed: int):
    """Build the Dataset a configuration's ``dataset`` block describes:
    ``generator`` (a function of this module), ``rows_per_client`` training
    rows for each of ``num_clients`` and ``test_size`` held out on top."""
    from fedtpu.data.tabular import Dataset

    gen = spec["generator"]
    test_size = float(spec.get("test_size", 0.2))
    train_rows = num_clients * int(spec["rows_per_client"])
    rows = int(round(train_rows / (1.0 - test_size)))
    if gen == "income_like":
        x, y, names = income_like(rows, seed)
        # The reference scales on the full data before it splits
        # (FL_CustomMLP...:235-239); the benchmark keeps that order.
        std = x.std(axis=0)
        x = ((x - x.mean(axis=0)) / np.where(std == 0.0, 1.0, std))
        x = x.astype(np.float32)
        classes = 2
    elif gen == "cifar_like":
        x, y = cifar_like(rows, seed)
        names = tuple(f"px{i}" for i in range(x.shape[1]))
        classes = 10
    else:
        raise KeyError(f"unknown dataset generator {gen!r}")
    x_tr, y_tr, x_te, y_te = _split(x, y, test_size, seed)
    return Dataset(
        x_train=x_tr, y_train=y_tr, x_test=x_te, y_test=y_te,
        num_classes=classes, feature_names=names,
        label_classes=np.arange(classes),
        source={"kind": "synthetic", "generator": f"perfbench.{gen}",
                "rows": int(rows), "seed": int(seed)})
