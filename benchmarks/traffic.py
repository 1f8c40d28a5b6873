"""The one general traffic generator: a cell's ``traffic`` parameters (a data
file) and ``--seed`` give the stacked per-client batches of a federated
round. Every seed has the SAME multiset of row lengths, in another order, so
the seed moves no work; ids and labels are drawn from the seed.

Parameters (the cell file's ``traffic`` object):
  clients, local_batches, batch, seq     the shapes
  vocab                                  ids are drawn below it (from the configuration)
  full_share, min_len                    this share of rows fills ``seq``;
                                         the rest are spread evenly over
                                         [min_len, seq)
  label_signal                           share of a row's tokens drawn from
                                         its label's band of the vocabulary
                                         (classification only)
  job                                    the job kind, where the cell sets one
                                         of its own; otherwise the model
                                         family's (``task`` of its
                                         ``program(sizes)``): "classification"
                                         (ids, mask, labels; a [CLS] first) or
                                         "causal_lm" (ids, mask and the all-ones example
                                         mask the loss reads; no label; every
                                         id drawn below the vocabulary the
                                         configuration holds)
"""

from __future__ import annotations

import numpy as np


def row_lengths(n_rows, seq, full_share, min_len):
    n_full = int(round(n_rows * full_share))
    rest = n_rows - n_full
    short = (np.linspace(min_len, seq - 1, rest).round().astype(np.int64)
             if rest else np.zeros((0,), np.int64))
    return np.concatenate([np.full((n_full,), seq, np.int64), short])


JOBS = ("classification", "causal_lm")


def make(params, vocab, num_labels, seed, job="classification"):
    """``(batches, n_ex)``: leaves [C, steps, B, ...] as numpy arrays, and the
    number of examples each client trains on in a round."""
    job = params.get("job", job)
    if job not in JOBS:
        raise ValueError(f"unknown job kind {job!r}; the generator knows {JOBS}")
    C, T, B, S = (params["clients"], params["local_batches"], params["batch"],
                  params["seq"])
    rng = np.random.default_rng([int(seed), 0xBE7C])
    n_rows = C * T * B
    lengths = row_lengths(n_rows, S, params["full_share"], min(params["min_len"], S - 1))
    lengths = rng.permutation(lengths).reshape(C, T, B)
    pos = np.arange(S)[None, None, None, :]
    mask = (pos < lengths[..., None]).astype(np.int32)
    n_ex = np.full((C,), float(T * B), np.float32)
    if job == "causal_lm":
        # next-token training reads nothing but the ids: no label, no [CLS]
        ids = rng.integers(4, vocab, (C, T, B, S))
        ids = np.where(mask > 0, ids, 0).astype(np.int32)
        return {"ids": ids, "mask": mask,
                "example_mask": np.ones((C, T, B), np.float32)}, n_ex
    labels = rng.integers(0, num_labels, (C, T, B))
    ids = rng.integers(4, vocab, (C, T, B, S))
    band = 64  # tokens of a label's band: [4 + label*band, 4 + (label+1)*band)
    signal = 4 + labels[..., None] * band + rng.integers(0, band, (C, T, B, S))
    ids = np.where(rng.random((C, T, B, S)) < params["label_signal"], signal, ids)
    ids = np.where(mask > 0, ids, 0).astype(np.int32)
    ids[..., 0] = 2  # [CLS]
    batches = {
        "ids": ids, "mask": mask, "labels": labels.astype(np.int32),
        "example_mask": np.ones((C, T, B), np.float32),
    }
    return batches, n_ex


def tokens_per_round(params):
    return params["clients"] * params["local_batches"] * params["batch"] * params["seq"]
