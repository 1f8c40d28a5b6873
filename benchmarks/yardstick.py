"""The yardstick's arithmetic: the table of peaks, the rule by which the
operations a training step REQUIRES are counted (from the configuration's
shapes, never from the program; the count itself lives with the model's
family, benchmarks/families/<family>), and the roofline share of a kernel.
Later PRs cannot change this file."""

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


def train_flops(forward_flops, trained=True):
    """THE RULE, written once: count what the training result REQUIRES at the
    sizes held here. A product with a matrix that is trained costs its
    forward product, an activation-gradient product and a weight-gradient
    product: three times ``forward_flops``. A matrix that is NOT trained (a
    frozen base under adapters) has the first two and no weight-gradient
    product: twice. A product of two activations (attention's QK^T and PV)
    has a gradient for each operand: three times. An expert counts for the
    tokens routed to it among the experts held, not for every token;
    embedding lookups are gathers and count nothing; recomputation counts
    nothing. A family's ``train_flops_per_token`` adds its products up by
    this function."""
    return (3.0 if trained else 2.0) * forward_flops


def mfu_pct(tokens_per_s_per_chip, sizes, seq, device_kind, cell=None):
    """The whole step's share of the chip's peak, over the operations the
    family of the configuration's file counts as required."""
    from benchmarks import families

    required = families.of(sizes).train_flops_per_token(sizes, seq, cell)
    return 100.0 * tokens_per_s_per_chip * required / peaks(device_kind)["bf16_flops_per_s"]


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
