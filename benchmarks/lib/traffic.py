"""The one general generator of training traffic: token data from a seed.

A cell's workload file (``benchmarks/workloads/<cell>.json``) carries the
parameters under ``"traffic"``; this module turns them and ``--seed`` into
the arrays the program's feed takes.  A new mix is a new data file:

    {"objective": "causal_lm" | "mlm",
     "seq_len": 1024, "batch": 8, "steps_per_round": 8, "val_steps": 2,
     "zipf_offset": 10.0, "zipf_exponent": 1.0, "doc_len_mean": 300,
     "mask_rate": 0.15}

Every worker (chip) gets ``steps_per_round * batch`` training sequences and
``val_steps * batch`` validation sequences, all drawn afresh: no two rows
are alike.  Token ids follow a Zipf-like law over the configuration's whole
vocabulary (``p(rank) ~ 1 / (rank + offset) ** exponent``, ranks shuffled
from the seed so that frequent tokens are spread over the table), cut into
documents of geometric length joined by an end-of-text id, as packed
pre-training data is.  ``causal_lm`` labels are the inputs shifted left
with the last position ignored (-1); ``mlm`` masks ``mask_rate`` of the
positions BERT's way (80% [MASK], 10% random, 10% kept) and labels only
those, every other label -1.
"""

from __future__ import annotations

import numpy as np

IGNORE = -1


def _tokens(rng: np.random.Generator, n: int, seq_len: int, vocab: int,
            eot: int, t: dict) -> np.ndarray:
    ranks = np.arange(vocab, dtype=np.float64)
    p = 1.0 / (ranks + float(t.get("zipf_offset", 10.0))) ** float(
        t.get("zipf_exponent", 1.0))
    p /= p.sum()
    table = rng.permutation(vocab)
    toks = table[rng.choice(vocab, size=(n, seq_len), p=p)]
    doc_len_mean = float(t.get("doc_len_mean", 0))
    if doc_len_mean > 0:
        toks = np.where(rng.random((n, seq_len)) < 1.0 / doc_len_mean,
                        eot, toks)
    return toks.astype(np.int32)


def make_split(rng: np.random.Generator, n: int, vocab: int, t: dict,
               special: dict) -> tuple[np.ndarray, np.ndarray]:
    """``n`` sequences -> (inputs [n, L] int32, labels [n, L] int32)."""
    seq_len = int(t["seq_len"])
    toks = _tokens(rng, n, seq_len, vocab, int(special["eot"]), t)
    if t["objective"] == "causal_lm":
        labels = np.concatenate(
            [toks[:, 1:], np.full((n, 1), IGNORE, np.int32)], axis=1)
        return toks, labels
    if t["objective"] == "mlm":
        chosen = rng.random((n, seq_len)) < float(t["mask_rate"])
        how = rng.random((n, seq_len))
        inputs = np.where(chosen & (how < 0.8), int(special["mask"]), toks)
        inputs = np.where(chosen & (how >= 0.8) & (how < 0.9),
                          rng.integers(0, vocab, (n, seq_len)), inputs)
        labels = np.where(chosen, toks, IGNORE)
        return inputs.astype(np.int32), labels.astype(np.int32)
    raise ValueError(f"unknown objective {t['objective']!r}")


def generate(traffic: dict, config: dict, seed: int, workers: int) -> dict:
    """All the rows of one run: ``{"train": (x, y), "val": (x, y)}`` with
    ``workers * steps * batch`` rows each, worker-major and in feed order
    (the program's first round takes contiguous blocks in this order)."""
    rng = np.random.default_rng(int(seed))
    vocab = int(config["vocab_size"])
    special = config["special_tokens"]
    n_train = workers * int(traffic["steps_per_round"]) * int(traffic["batch"])
    n_val = workers * int(traffic["val_steps"]) * int(traffic["batch"])
    return {"train": make_split(rng, n_train, vocab, traffic, special),
            "val": make_split(rng, n_val, vocab, traffic, special)}


def keep_first_steps(labels: np.ndarray, steps: int, traffic: dict,
                     workers: int) -> np.ndarray:
    """A copy of the training labels in which only each worker's first
    ``steps`` batches count: every later row gets the ignore label
    throughout, which the loss weights by 0.  The compiled round is the
    window's own; the steps past ``steps`` then leave the state as it is
    (PERF.md, "How `correct` is decided here")."""
    per = int(traffic["steps_per_round"]) * int(traffic["batch"])
    out = labels.copy().reshape(workers, per, -1)
    out[:, steps * int(traffic["batch"]):] = IGNORE
    return out.reshape(labels.shape)
