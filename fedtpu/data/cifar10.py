"""CIFAR-10 loader for the ConvNet stress config (BASELINE.json #5).

No reference analogue — the reference ships exactly one tabular CSV
(SURVEY.md §0). This loader reads the standard CIFAR-10 python pickle batches
(``cifar-10-batches-py``) from a local directory if present; in zero-egress
environments (no download possible) it falls back to a deterministic
synthetic image set with CIFAR shapes, so the full pipeline — packing,
sharding, ConvNet FedAvg — exercises identically either way.
"""

from __future__ import annotations

import os
import pickle
from typing import Optional, Tuple

import numpy as np

from fedtpu.data.tabular import Dataset

_CANDIDATES = ("cifar-10-batches-py", "data/cifar-10-batches-py",
               "/root/data/cifar-10-batches-py")


def find_cifar10_dir(root: Optional[str] = None) -> Optional[str]:
    for cand in ((root,) if root else _CANDIDATES):
        if cand and os.path.isdir(cand) and \
                os.path.exists(os.path.join(cand, "data_batch_1")):
            return cand
    return None


def _load_batch(path: str) -> Tuple[np.ndarray, np.ndarray]:
    with open(path, "rb") as f:
        blob = pickle.load(f, encoding="bytes")
    x = blob[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)  # NHWC
    y = np.asarray(blob[b"labels"], np.int32)
    return x, y


def synthetic_cifar_like(rows: int, seed: int = 11,
                         image_shape=(32, 32, 3), classes: int = 10,
                         center_scale: float = 0.12,
                         noise_std: float = 0.5,
                         label_noise: float = 0.15):
    """Class-conditioned Gaussian blobs + label noise — deterministic,
    CIFAR-shaped, and NON-separable by construction (VERDICT r3 #5: the
    round-3 generator's wide centers saturated the config-5 benchmark at
    accuracy 1.0 by round 38, a smoke test wearing benchmark clothes).

    ``center_scale`` sets the class overlap: pairwise center distance is
    ~``center_scale * sqrt(2 * dim)`` against per-direction noise std
    ``noise_std``. Defaults calibrated on the v5e (round 4): 0.04 left
    the 300-round config-5 trajectory at 0.20 (too hard), 0.08 at 0.58,
    0.12 plateaus at ~0.81 by round ~200 — learnable, sub-cap, and
    falsifiable (the label-noise ceiling is ~0.865).
    ``label_noise`` uniformly re-draws that fraction of labels
    (including possibly the true one), capping reachable accuracy well
    below 1.0 unless the model memorizes individual flipped points.
    ``center_scale=1.0, label_noise=0.0`` reproduces the old separable
    smoke-test distribution."""
    rng = np.random.default_rng(seed)
    y = np.arange(rows) % classes
    rng.shuffle(y)
    h, w, ch = image_shape
    centers = rng.normal(0.0, center_scale, size=(classes, h, w, ch))
    x = centers[y] + rng.normal(0.0, noise_std, size=(rows, h, w, ch))
    y_obs = y.copy()
    if label_noise > 0:
        flip = rng.random(rows) < label_noise
        y_obs[flip] = rng.integers(0, classes, int(flip.sum()))
    return x.astype(np.float32), y_obs.astype(np.int32)


def load_cifar10(root: Optional[str] = None, flatten: bool = True,
                 synthetic_rows: int = 4096) -> Dataset:
    """Return a Dataset with CIFAR-10 train/test (real if the pickle batches
    exist locally, synthetic otherwise). ``flatten=True`` packs images as
    (N, 3072) rows so the tabular sharding/packing path applies unchanged;
    the ConvNet apply reshapes back to NHWC (fedtpu.models.convnet)."""
    d = find_cifar10_dir(root)
    if d is not None:
        xs, ys = zip(*(_load_batch(os.path.join(d, f"data_batch_{i}"))
                       for i in range(1, 6)))
        x_train = np.concatenate(xs).astype(np.float32) / 255.0
        y_train = np.concatenate(ys)
        x_test, y_test = _load_batch(os.path.join(d, "test_batch"))
        x_test = x_test.astype(np.float32) / 255.0
        y_test = np.asarray(y_test, np.int32)
        source = {"kind": "cifar10", "path": d,
                  "rows": int(len(x_train) + len(x_test))}
    else:
        x, y = synthetic_cifar_like(synthetic_rows)
        source = {"kind": "synthetic", "generator": "synthetic_cifar_like",
                  "rows": int(len(x))}
        n_test = max(1, len(x) // 5)
        x_train, y_train = x[:-n_test], y[:-n_test]
        x_test, y_test = x[-n_test:], y[-n_test:]

    if flatten:
        x_train = x_train.reshape(len(x_train), -1)
        x_test = x_test.reshape(len(x_test), -1)

    return Dataset(
        x_train=x_train, y_train=y_train.astype(np.int32),
        x_test=x_test, y_test=y_test.astype(np.int32),
        num_classes=10,
        feature_names=tuple(f"px{i}" for i in range(x_train.shape[1])),
        label_classes=np.arange(10),
        source=source,
    )
