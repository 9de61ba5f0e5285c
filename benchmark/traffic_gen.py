"""Training traffic: a token stream made from ``--seed``.

One generator for every cell; a traffic mix is a file of its parameters
under ``benchmark/traffic/``. The stream is not noise, so that a loss
can fall and a broken update shows:

- unigrams follow a Zipf law over the configuration's real vocabulary
  (``vocab_used`` tokens; padded rows of the embedding never occur),
  with the ranks scattered over the token ids by a seeded permutation;
- a first-order rule: with probability ``follow_probability`` the next
  token is a fixed permutation of the previous one, else a fresh draw;
- documents have log-normal lengths, end in EOS and are packed back to
  back into rows of ``seq`` tokens, so most rows hold several document
  boundaries, as pre-training rows do. Labels are the next token.

Everything is vectorised: the pool of a four-chip cell is 17 million
tokens and counts as set-up.
"""
from __future__ import annotations

import numpy as np

REQUIRED = ("seq", "pool_batches", "zipf_exponent", "follow_probability",
            "doc_length_median", "doc_length_sigma", "doc_length_min")


def token_stream(n: int, params: dict, vocab_used: int, eos: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``n`` tokens of packed documents, int32."""
    words = np.delete(np.arange(vocab_used, dtype=np.int32), eos)
    of_rank = rng.permutation(words)                  # rank -> token id
    succ = np.arange(vocab_used, dtype=np.int32)      # token -> its follower
    succ[words] = rng.permutation(words)

    # n independent Zipf draws, the quick way: how often each rank occurs
    # is multinomial, and a uniform shuffle puts the occurrences in order
    weights = 1.0 / np.arange(1, len(words) + 1) ** params["zipf_exponent"]
    out = np.repeat(of_rank, rng.multinomial(n, weights / weights.sum()))
    rng.shuffle(out)

    # document ends: EOS at the last position of each document
    mean_len = params["doc_length_median"] * np.exp(
        params["doc_length_sigma"] ** 2 / 2)
    lengths = np.empty(0, np.int64)
    while lengths.sum() < n:
        more = rng.lognormal(np.log(params["doc_length_median"]),
                             params["doc_length_sigma"],
                             int(n / mean_len * 1.2) + 16)
        lengths = np.concatenate([lengths, np.maximum(
            more.astype(np.int64), params["doc_length_min"])])
    ends = np.cumsum(lengths) - 1
    ends = ends[ends < n]
    out[ends] = eos

    # the first-order rule, applied run by run: a position that follows
    # takes succ[] of its left neighbour. Runs are geometric, so the
    # k-th pass touches 2**-k of the stream.
    follows = rng.random(n, dtype=np.float32) < params["follow_probability"]
    follows[0] = False
    follows[ends] = False                              # EOS is not a follower
    follows[np.minimum(ends + 1, n - 1)] = False       # nor a document's start
    idx = np.arange(n, dtype=np.int32)
    start = np.maximum.accumulate(np.where(follows, np.int32(0), idx))
    depth = idx - start                                # 0 where not following
    for k in range(1, int(depth.max()) + 1):
        at = np.nonzero(depth == k)[0]
        out[at] = succ[out[at - 1]]
    return out


def make_pool(params: dict, vocab_used: int, eos: int, rows: int, seed: int):
    """``(ids, labels)``, each ``(pool_batches, rows, seq)`` int32: the
    global batches a run cycles through."""
    missing = [k for k in REQUIRED if k not in params]
    if missing:
        raise KeyError(f"traffic file lacks {missing}")
    seq, pool = params["seq"], params["pool_batches"]
    n_rows = pool * rows
    rng = np.random.default_rng(seed)
    stream = token_stream(n_rows * seq + 1, params, vocab_used, eos, rng)
    ids = stream[:-1].reshape(pool, rows, seq)
    labels = stream[1:].reshape(pool, rows, seq)
    return np.ascontiguousarray(ids), np.ascontiguousarray(labels)
