"""The yardstick's arithmetic: the table of peaks, the operations a training
step REQUIRES (from the configuration's shapes, never from the program), and
the roofline share of a kernel. Later PRs cannot change this file."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The peaks of one chip of ``device_kind``. A kind that the table does
    not hold is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(it holds {sorted(table)}): add its row with a source")
    return table[device_kind]


def forward_flops_per_token(sizes, seq):
    """Matrix-multiplication and attention FLOP of one forward pass, per
    token, at sequence length ``seq``. A layer counts as often as it RUNS
    (ALBERT's shared layer num_hidden_layers times); embedding lookups are
    gathers and count nothing; the pooler and the classifier run once a
    sequence."""
    H, F, E = sizes["hidden_size"], sizes["intermediate_size"], sizes["embedding_size"]
    L = sizes["num_hidden_layers"]
    layer = 2 * (4 * H * H + 2 * H * F)  # q, k, v, out and the two MLP products
    attention = 4 * seq * H              # QK^T and PV over every head
    proj = 2 * E * H if E != H else 0    # ALBERT's factorized embedding
    head = (2 * H * H + 2 * H * sizes["num_labels"]) / seq
    return L * (layer + attention) + proj + head


def train_flops_per_token(sizes, seq):
    """Forward, and a backward that costs twice the forward (one product for
    the activation gradient, one for the weight gradient). Recomputation
    counts nothing."""
    return 3.0 * forward_flops_per_token(sizes, seq)


def mfu_pct(tokens_per_s_per_chip, sizes, seq, device_kind):
    return 100.0 * tokens_per_s_per_chip * train_flops_per_token(sizes, seq) / (
        peaks(device_kind)["bf16_flops_per_s"])


def roofline(flops, bytes_moved, seconds, device_kind, flops_key="bf16_flops_per_s"):
    """``(share_pct, bound)`` of a kernel that needs ``flops`` operations and
    ``bytes_moved`` bytes of HBM traffic and took ``seconds`` on the device:
    the least time the chip could take over the time it took, and which of
    the two peaks sets that least time. None where nothing was measured:
    a share is never reported as 0."""
    if not seconds or seconds <= 0:
        return None
    pk = peaks(device_kind)
    t_compute = flops / pk[flops_key]
    t_memory = bytes_moved / pk["hbm_bytes_per_s"]
    least = max(t_compute, t_memory)
    return 100.0 * least / seconds, ("compute" if t_compute >= t_memory else "memory")
