"""The language-model cells' data, from ``--seed``: a federated corpus of
packed token sequences. The benchmark's own copy of the law the program's
stand-in follows (``fedtpu.data.tokens``), kept here so that the yardstick
does not move when the program's generator does:

* documents of lognormal length (median 600 tokens, sigma 1, clipped to
  16..sequence length), packed whole and greedily into sequences; what is
  left of a sequence is padding (segment 0, token 0);
* tokens by a Zipf(1.1) law over a ranking of the vocabulary; 70% of a
  client's tokens go through its own topic's permutation of the ranking and
  30% through one shared by all;
* clients hold different numbers of sequences: a ramp from half the mean to
  one and a half times it (16 over 8 clients: 1, 1, 2, 2, 2, 2, 3, 3).

Returns the program's input type with ``client_of_row`` saying whose each
row is and ``source`` naming this generator.
"""

from __future__ import annotations

import numpy as np

ZIPF, TOPIC_SHARE = 1.1, 0.7
DOC_MEDIAN, DOC_SIGMA, DOC_MIN = 600.0, 1.0, 16


def client_sizes(rows: int, clients: int) -> np.ndarray:
    ramp = np.linspace(0.5, 1.5, clients) * rows / clients
    sizes = np.maximum(1, np.floor(ramp).astype(int))
    for c in np.argsort(-(ramp - np.floor(ramp)), kind="stable"):
        if sizes.sum() >= rows:
            break
        sizes[c] += 1
    while sizes.sum() > rows:
        sizes[np.argmax(sizes)] -= 1
    return sizes


def pack(rng, n_seqs: int, seq_len: int, draw) -> np.ndarray:
    out = np.zeros((n_seqs, 2, seq_len), np.int32)
    for s in range(n_seqs):
        at, seg = 0, 0
        while True:
            n = int(np.clip(rng.lognormal(np.log(DOC_MEDIAN), DOC_SIGMA),
                            DOC_MIN, seq_len))
            if at + n > seq_len:
                break
            seg += 1
            out[s, 0, at:at + n] = draw(n)
            out[s, 1, at:at + n] = seg
            at += n
    return out


def token_corpus(clients: int, rows: int, seq_len: int, vocab: int, seed: int,
                 test_rows: int):
    """``(x_train (rows, 2, T), client_of_row (rows,), x_test)``."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, vocab, dtype=np.float64) ** -ZIPF)
    cdf /= cdf[-1]
    shared = rng.permutation(vocab - 1) + 1

    def drawer(topic):
        def draw(n):
            rank = np.searchsorted(cdf, rng.random(n))
            return np.where(rng.random(n) < TOPIC_SHARE, topic[rank],
                            shared[rank])
        return draw

    sizes = client_sizes(rows, clients)
    x = np.concatenate([pack(rng, int(n), seq_len,
                             drawer(rng.permutation(vocab - 1) + 1))
                        for n in sizes])
    return (x, np.repeat(np.arange(clients), sizes),
            pack(rng, test_rows, seq_len, drawer(shared)))


def make(spec: dict, clients: int, vocab: int, seed: int):
    """The Dataset a configuration's ``dataset`` block describes."""
    from fedtpu.data.tabular import Dataset

    if spec["generator"] != "token_corpus":
        raise KeyError(f"unknown dataset generator {spec['generator']!r}")
    rows, seq_len = int(spec["rows"]), int(spec["sequence_length"])
    test_rows = max(1, int(np.ceil(rows * float(spec.get("test_size", 0.125)))))
    x, owner, x_test = token_corpus(clients, rows, seq_len, vocab, seed,
                                    test_rows)
    return Dataset(
        x_train=x, y_train=np.zeros(len(x), np.int32), x_test=x_test,
        y_test=np.zeros(len(x_test), np.int32), num_classes=vocab,
        feature_names=("tokens", "segments"), label_classes=np.arange(vocab),
        client_of_row=owner,
        source={"kind": "synthetic", "generator": "perfbench.token_corpus",
                "rows": rows, "seed": int(seed), "sequence_length": seq_len})


def counts(x: np.ndarray) -> dict:
    """What the cost of a round is computed from, measured on the rows:
    positions, real tokens, tokens counted in the loss, padding, and the
    (query, key) pairs causal attention within a document needs."""
    segs = x[:, 1]
    real = segs > 0
    nxt = np.concatenate([segs[:, 1:], np.zeros((len(segs), 1), segs.dtype)], 1)
    pairs = 0
    for row in segs:
        lengths = np.bincount(row[row > 0])
        pairs += int((lengths * (lengths + 1) // 2).sum())
    return {"sequences": int(len(x)), "positions": int(segs.size),
            "tokens": int(real.sum()),
            "counted": int((real & (nxt == segs)).sum()),
            "padding": int((~real).sum()), "attention_pairs": pairs}
