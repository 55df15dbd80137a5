"""A seeded synthetic token corpus, federated: what stands in for private
documents where none can be shipped (``Dataset.source`` says so).

* **Documents** have lognormal lengths (median about 600 tokens, clipped to
  16..sequence length): heavy-tailed, as collections of notes, reports and
  messages are.
* **Tokens** follow a Zipf(1.1) law over a ranking of the vocabulary. Every
  client has a *topic*, a permutation of that ranking of its own: 70% of a
  client's tokens go through its topic's permutation and 30% through one all
  clients share, so the clients' frequent tokens differ (and with them the
  experts a sparse model routes to) while a common core remains.
* **Packing** is greedy and keeps documents whole: a document that does not
  fit the rest of a sequence starts the next one and the rest is padding
  (segment id 0; token 0 is kept for it). A row is ``(2, T)`` int32: token
  ids and segment ids counted from 1 within the row.
* **Size skew**: clients hold different numbers of sequences, a ramp from
  half the mean to one and a half times it.

Rows are stored client after client and ``client_of_row`` says whose each
is: the corpus is born partitioned, and ``pack_clients`` keeps it so.
"""

from __future__ import annotations

import numpy as np

from fedtpu.data.tabular import Dataset

ZIPF_EXPONENT = 1.1
TOPIC_SHARE = 0.7
DOC_MEDIAN, DOC_SIGMA, DOC_MIN = 600.0, 1.0, 16


def skewed_sizes(rows: int, clients: int) -> np.ndarray:
    """``rows`` sequences over ``clients``: a ramp from half the mean to one
    and a half times it, in whole sequences, at least one each."""
    if rows < clients:
        raise ValueError(f"{rows} sequences cannot give each of {clients} "
                         "clients one")
    ramp = np.linspace(0.5, 1.5, clients) * rows / clients
    sizes = np.maximum(1, np.floor(ramp).astype(int))
    # hand out what rounding left, largest remainders first (from the top)
    for c in np.argsort(-(ramp - np.floor(ramp)), kind="stable"):
        if sizes.sum() >= rows:
            break
        sizes[c] += 1
    while sizes.sum() > rows:
        sizes[np.argmax(sizes)] -= 1
    return sizes


def _pack(rng, n_seqs: int, seq_len: int, draw_tokens) -> np.ndarray:
    """``(n_seqs, 2, seq_len)``: whole documents packed greedily."""
    out = np.zeros((n_seqs, 2, seq_len), np.int32)
    for s in range(n_seqs):
        at, seg = 0, 0
        while True:
            n = int(np.clip(rng.lognormal(np.log(DOC_MEDIAN), DOC_SIGMA),
                            DOC_MIN, seq_len))
            if at + n > seq_len:
                break
            seg += 1
            out[s, 0, at:at + n] = draw_tokens(n)
            out[s, 1, at:at + n] = seg
            at += n
    return out


def synthetic_token_corpus(num_clients: int, rows: int, seq_len: int,
                           vocab_size: int, seed: int = 0,
                           test_size: float = 0.2,
                           generator: str = "fedtpu.data.tokens") -> Dataset:
    """``rows`` training sequences of ``seq_len`` tokens over ``num_clients``
    clients (``skewed_sizes``), and ``ceil(rows * test_size)`` held-out
    sequences drawn as a client of the shared topic alone would."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size, dtype=np.float64)      # id 0 is padding
    cdf = np.cumsum(ranks ** -ZIPF_EXPONENT)
    cdf /= cdf[-1]
    shared = rng.permutation(vocab_size - 1) + 1

    def drawer(topic):
        def draw(n):
            rank = np.searchsorted(cdf, rng.random(n))
            own = rng.random(n) < TOPIC_SHARE
            return np.where(own, topic[rank], shared[rank])
        return draw

    sizes = skewed_sizes(rows, num_clients)
    parts = [_pack(rng, int(n), seq_len,
                   drawer(rng.permutation(vocab_size - 1) + 1))
             for n in sizes]
    x_test = _pack(rng, int(np.ceil(rows * test_size)), seq_len,
                   drawer(shared))
    x_train = np.concatenate(parts)
    return Dataset(
        x_train=x_train, y_train=np.zeros(len(x_train), np.int32),
        x_test=x_test, y_test=np.zeros(len(x_test), np.int32),
        num_classes=vocab_size, feature_names=("tokens", "segments"),
        label_classes=np.arange(vocab_size),
        client_of_row=np.repeat(np.arange(num_clients), sizes),
        source={"kind": "synthetic", "generator": generator,
                "rows": int(rows), "seed": int(seed),
                "sequence_length": int(seq_len),
                "sequences_per_client": [int(n) for n in sizes]})
