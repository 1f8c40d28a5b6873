"""The operations a trained token REQUIRES of the latent-attention expert
decoder under LoRA, by ``yardstick.train_flops``'s rule, and the grouped
expert product's operations and bytes for its roofline share: from the
configuration's sizes and the program's counters, the same whatever
implements the product."""

from __future__ import annotations

from benchmarks import yardstick

from . import weights


def real_share(cell):
    """The share of a cell's positions that hold a token (the traffic
    generator's row lengths); 1 where no cell is named."""
    if not cell:
        return 1.0
    from benchmarks import traffic

    t = cell["traffic"]
    n = t["clients"] * t["local_batches"] * t["batch"]
    lengths = traffic.row_lengths(n, t["seq"], t["full_share"], min(t["min_len"], t["seq"] - 1))
    return float(lengths.sum()) / (n * t["seq"])


def products(sizes, seq, cell=None):
    """``[(what, forward FLOP a position, trained?), ...]`` of one position's
    forward pass at the sizes held. A frozen matrix: its product, not
    trained (no weight-gradient product). An adapter's two factors: trained.
    Attention's two products of activations over the mean causal length
    (``(seq + 1) / 2`` keys a query): a gradient for each operand, as a
    trained one. A routed expert: the assignments that fall on held experts,
    in expectation ``k * G / E`` a REAL position (a padded one is routed to
    no expert: ``real_share``), frozen. The router: frozen. The embedding is
    a gather and counts nothing."""
    d = weights.dims(sizes)
    r = d["r"]
    out = []
    shapes = weights.matrix_shapes(sizes)
    for name in weights.ADAPTED + ("r",):
        fi, fo = shapes[name]
        group = "attention" if name in ("dq", "uq", "dkv", "ukv", "o") else (
            "router" if name == "r" else "shared expert")
        out.append((group, d["L"] * 2 * fi * fo, False))
        if name != "r":
            out.append(("adapters", d["L"] * 2 * r * (fi + fo), True))
    keys = (seq + 1) / 2.0
    out.append(("attention", d["L"] * 2 * keys * d["heads"] * ((d["dn"] + d["dr"]) + d["dv"]), True))
    held_per_token = d["k"] * d["G"] / d["E"] * real_share(cell)
    out.append(("routed experts", d["L"] * held_per_token * 3 * 2 * d["H"] * d["F"], False))
    out.append(("head", 2 * d["H"] * d["V"], False))
    out.append(("adapters", 2 * r * (d["H"] + d["V"]), True))
    return out


def forward_flops_per_token(sizes, seq):
    return sum(f for _, f, _ in products(sizes, seq))


def train_flops_per_token(sizes, seq, cell=None):
    return sum(yardstick.train_flops(f, trained) for _, f, trained in products(sizes, seq, cell))


def by_group(sizes, seq, cell=None):
    """``{group: (forward, required) FLOP a token}`` for PERF.md's table."""
    acc = {}
    for what, f, trained in products(sizes, seq, cell):
        a = acc.setdefault(what, [0.0, 0.0])
        a[0] += f
        a[1] += yardstick.train_flops(f, trained)
    return {k: tuple(v) for k, v in acc.items()}


def grouped_matmul_work(sizes, slots_held, steps):
    """``(FLOP, bytes)`` the routed experts' grouped products REQUIRE for
    ``slots_held`` assignments on held experts, summed over ``steps``
    client-steps-times-layers each of which reads the held weights: three
    projections, forward and the activation-gradient product (the weights
    are frozen), so six products of ``2 * rows * H * F`` operations; each
    reads the held experts' weights once (``G * H * F`` elements in the
    stored type) and a row in and a row out in the compute type."""
    d = weights.dims(sizes)
    item = 2  # bfloat16, parameters and activations
    flops = 6 * 2.0 * slots_held * d["H"] * d["F"]
    weight_bytes = 6 * steps * d["G"] * d["H"] * d["F"] * item
    row_bytes = 6 * slots_held * (d["H"] + d["F"]) * item
    return flops, weight_bytes + row_bytes


def flash_attention_work(sizes, seq, rows):
    """``(FLOP, bytes)`` that causal attention over ``rows`` rows of ``seq``
    positions REQUIRES in one layer, forward and backward: the two products
    of activations, each with a gradient for each operand (six products of
    ``2 * heads * seq * (seq + 1) / 2 * head`` operations a row; what a
    kernel computes again counts nothing); q, k, v read and o written
    forward, q, k, v, o, dO read and dq, dk, dv written backward, in the
    compute type."""
    d = weights.dims(sizes)
    head = d["dn"] + d["dr"]
    flops = 6 * 2.0 * rows * d["heads"] * (seq * (seq + 1) / 2.0) * head
    return flops, 12 * rows * d["heads"] * seq * head * 2
