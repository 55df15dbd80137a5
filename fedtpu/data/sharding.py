"""Client sharding: carve the train set into per-client shards and pack them
into dense ``(clients, samples, ...)`` arrays ready to lay out on the mesh.

Reference semantics being reproduced (and fixed):

* Contiguous chunking by rank, last rank takes the remainder
  (FL_CustomMLPCLassifierImplementation_Multiple_Rounds.py:48-61,
  FL_SkLearn_MLPClassifier_Limitation.py:17-22).
* The torch driver shuffles with an UNSEEDED ``np.random.permutation`` per
  rank (FL_CustomMLP...:53) — each rank permutes independently, so shards
  overlap and do not partition the data. fedtpu's default is a shared-seed
  permutation (a true partition); the bug is available behind
  ``unseeded_per_client_bug`` for parity experiments.
* Non-IID label-skew shards ('label_sort', 'dirichlet') are NEW — required by
  BASELINE.json config 4; the reference only shards IID-contiguously.

TPU-first design note: clients own different shard sizes (the remainder), but
XLA wants static shapes. We pad every shard to the max shard length and carry a
``(clients, samples)`` validity mask plus true per-client counts; masked loss /
metrics make padding invisible, and the true counts drive data-size-weighted
FedAvg exactly like ``len(X_local)`` does at FL_CustomMLP...:104-106.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from fedtpu.config import ShardConfig


@dataclasses.dataclass
class ClientBatch:
    """Dense, padded per-client data. Leading axis = clients; shard it over the
    ('clients',) mesh axis with a NamedSharding."""

    x: np.ndarray       # (C, N_pad, ...) float32
    y: np.ndarray       # (C, N_pad) int32
    mask: np.ndarray    # (C, N_pad) float32, 1.0 for real samples
    counts: np.ndarray  # (C,) int32 true shard sizes

    @property
    def num_clients(self) -> int:
        return self.x.shape[0]


def _contiguous_bounds(num_samples: int, num_clients: int):
    """Chunk bounds per FL_CustomMLP...:58-60: ``chunk = max(1, n // size)``,
    client c takes [c*chunk, (c+1)*chunk) and the last client the remainder."""
    chunk = max(1, num_samples // num_clients)
    bounds = []
    for c in range(num_clients):
        start = c * chunk
        end = start + chunk if c != num_clients - 1 else num_samples
        bounds.append((min(start, num_samples), min(max(end, start), num_samples)))
    return bounds


def _partition_view(cfg: ShardConfig):
    """Resolve the elastic-verification partition window (config.py): shard
    as-if ``partition_clients`` clients exist, keep the ``num_clients``-wide
    window at ``partition_offset``. Returns (full_cfg, offset) — full_cfg is
    the as-if config with the window fields cleared, or None when off."""
    if cfg.partition_clients <= 0:
        return None, 0
    if not (0 <= cfg.partition_offset
            and cfg.partition_offset + cfg.num_clients <= cfg.partition_clients):
        raise ValueError(
            f"partition window [{cfg.partition_offset}, "
            f"{cfg.partition_offset + cfg.num_clients}) exceeds "
            f"partition_clients={cfg.partition_clients}")
    full = dataclasses.replace(cfg, num_clients=cfg.partition_clients,
                               partition_clients=0, partition_offset=0)
    return full, cfg.partition_offset


def shard_indices(y: np.ndarray, cfg: ShardConfig) -> List[np.ndarray]:
    """Return per-client index arrays into the train set."""
    full, offset = _partition_view(cfg)
    if full is not None:
        return shard_indices(y, full)[offset:offset + cfg.num_clients]
    n = len(y)
    c = cfg.num_clients
    rng = np.random.default_rng(cfg.shard_seed)

    if cfg.strategy == "contiguous":
        if cfg.shuffle and cfg.unseeded_per_client_bug:
            # Reference bug parity: every client draws its own unseeded
            # permutation of the FULL set, then takes its contiguous chunk —
            # shards overlap (FL_CustomMLP...:52-61).
            out = []
            for client, (start, end) in enumerate(_contiguous_bounds(n, c)):
                perm = np.random.permutation(n)  # deliberately unseeded
                out.append(perm[start:end])
            return out
        perm = rng.permutation(n) if cfg.shuffle else np.arange(n)
        return [perm[start:end] for start, end in _contiguous_bounds(n, c)]

    if cfg.strategy == "label_sort":
        # Pathological non-IID: sort by label, chunk contiguously — each
        # client sees only one or two labels.
        order = np.argsort(y, kind="stable")
        return [order[start:end] for start, end in _contiguous_bounds(n, c)]

    if cfg.strategy == "dirichlet":
        # Standard federated non-IID benchmark sharding (Hsu et al. style):
        # for each class, split its samples across clients with proportions
        # drawn from Dirichlet(alpha). Small alpha => heavy label skew.
        classes = np.unique(y)
        client_idx = [[] for _ in range(c)]
        for k in classes:
            idx_k = rng.permutation(np.flatnonzero(y == k))
            props = rng.dirichlet(np.full(c, cfg.dirichlet_alpha))
            cuts = (np.cumsum(props)[:-1] * len(idx_k)).astype(int)
            for client, part in enumerate(np.split(idx_k, cuts)):
                client_idx[client].append(part)
        return [rng.permutation(np.concatenate(parts)) if parts else
                np.empty((0,), dtype=np.int64) for parts in client_idx]

    raise ValueError(f"unknown shard strategy {cfg.strategy!r}")


def pack_clients(x: np.ndarray, y: np.ndarray, cfg: ShardConfig,
                 pad_multiple: int = 8, client_of_row=None) -> ClientBatch:
    """Shard then pack into padded dense arrays (see module docstring).

    ``client_of_row`` (``Dataset.client_of_row``): the rows already belong
    to clients, and each client packs its own, in order. Integer rows (token
    ids) stay integers; everything else packs as float32.

    ``pad_multiple`` rounds the per-client sample axis up so its size stays
    friendly to XLA tiling (the 8-sublane dimension on TPU).

    Under a partition window (``partition_clients``, see ShardConfig) the
    pad length is computed over ALL partition shards — not just the kept
    window — so every kept row is bitwise-identical (padding included) to
    the corresponding row of the full pack.
    """
    full, offset = _partition_view(cfg)
    if client_of_row is not None:
        if full is not None:
            raise ValueError("a partition window cannot re-carve rows that "
                             "already belong to clients (client_of_row)")
        owner = np.asarray(client_of_row)
        if owner.max(initial=-1) >= cfg.num_clients:
            raise ValueError(
                f"the data name client {int(owner.max())} and the run has "
                f"{cfg.num_clients} clients")
        idx = [np.flatnonzero(owner == c) for c in range(cfg.num_clients)]
        max_n = max((len(i) for i in idx), default=0)
    elif full is not None:
        idx_all = shard_indices(y, full)
        idx = idx_all[offset:offset + cfg.num_clients]
        max_n = max((len(i) for i in idx_all), default=0)
    else:
        idx = shard_indices(y, cfg)
        max_n = max((len(i) for i in idx), default=0)
    max_n = max(1, -(-max_n // pad_multiple) * pad_multiple)

    feat_shape = x.shape[1:]
    c = cfg.num_clients
    xp = np.zeros((c, max_n) + feat_shape,
                  dtype=x.dtype if np.issubdtype(x.dtype, np.integer)
                  else np.float32)
    yp = np.zeros((c, max_n), dtype=np.int32)
    mask = np.zeros((c, max_n), dtype=np.float32)
    counts = np.zeros((c,), dtype=np.int32)
    for client, ids in enumerate(idx):
        k = len(ids)
        xp[client, :k] = x[ids]
        yp[client, :k] = y[ids]
        mask[client, :k] = 1.0
        counts[client] = k
    return ClientBatch(x=xp, y=yp, mask=mask, counts=counts)
