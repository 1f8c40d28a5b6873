"""The operations a trained token REQUIRES of the Gated DeltaNet and gated
attention decoder with experts under LoRA, by ``yardstick.train_flops``'s
rule, and the operations and bytes of the gated delta rule for its roofline
share: from the configuration's sizes and the program's counter, the same
whatever implements it."""

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


def rule_forward_flops_per_position(sizes):
    """One position's forward operations in one mixer's gated delta rule, in
    the chunked form's products counted once a value head, a chunk's
    lower-triangular products over the mean causal length ``(chunk + 1) /
    2``: ``K K^T``, the triangular solve (``chunk^2 / 3`` a position), ``T
    (beta V)``, ``T (beta exp(G) K)``, ``Q K^T``, ``W S``, ``Q S``, the
    intra-chunk ``P V'`` and the state's ``K^T V'``."""
    d = weights.dims(sizes)
    causal = (d["chunk"] + 1) / 2.0
    Dk, Dv = d["Dk"], d["Dv"]
    per_head = (2 * causal * Dk  # K K^T
                + d["chunk"] ** 2 / 3.0  # the solve
                + 2 * causal * Dv + 2 * causal * Dk  # T (beta V), T (beta exp(G) K)
                + 2 * causal * Dk  # Q K^T
                + 3 * 2 * Dk * Dv  # W S, Q S, K^T V'
                + 2 * causal * Dv)  # P V'
    return d["Hv"] * per_head


def products(sizes, seq, cell=None):
    """``[(what, forward FLOP a position, trained?), ...]`` of one position's
    forward pass at the sizes held. A frozen matrix: its product, not
    trained (no weight-gradient product). An adapter's two factors: trained.
    Products of two activations (attention's two over the mean causal length
    ``(seq + 1) / 2``, the rule's): a gradient for each operand, as a trained
    one. A routed expert: the assignments that fall on held experts, in
    expectation ``k * G / E`` a REAL position (a padded one is routed to no
    expert: ``real_share``), frozen. The router, the shared expert's gate,
    ``in_proj_ba`` and the depthwise convolution: frozen. The embedding is a
    gather and counts nothing."""
    d = weights.dims(sizes)
    r = d["r"]
    out = []
    held_per_token = d["k"] * d["G"] / d["E"] * real_share(cell)
    for kind in d["kinds"]:
        shapes = weights.matrix_shapes(sizes, kind)
        for name, (fi, fo) in shapes.items():
            group = ("router" if name == "r" else
                     "shared expert" if name in ("sg", "su", "sd", "sgate") else
                     "Gated DeltaNet mixer" if kind == weights.LINEAR else "attention")
            out.append((group, 2 * fi * fo, False))
            if name in weights.ADAPTED[kind]:
                out.append(("adapters", 2 * r * (fi + fo), True))
        if kind == weights.LINEAR:
            out.append(("Gated DeltaNet mixer", rule_forward_flops_per_position(sizes), True))
            out.append(("Gated DeltaNet mixer", 2 * d["K"] * d["conv"], False))
        else:
            keys = (seq + 1) / 2.0
            out.append(("attention", 2 * keys * d["heads"] * 2 * d["hd"], True))
        out.append(("routed experts", held_per_token * 3 * 2 * d["H"] * d["F"], False))
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


def gated_delta_work(sizes, chunks):
    """``(FLOP, bytes)`` the gated delta rule REQUIRES for ``chunks`` chunks
    of ``gdn_chunk_size`` positions of a row (the program's counter
    ``gdn_chunks``: rows x chunks a row, summed over client steps and
    layers), forward and backward: products of activations, so three times
    the forward operations (a gradient for each operand; the chunk states and
    products that a backward pass computes again count nothing). Bytes:
    ``q``, ``k``, ``v``, ``g`` and ``beta`` read and ``o`` written once in the
    forward pass; the same five and ``do`` read and their five gradients
    written in the backward pass, in the compute type (``g`` and ``beta``
    float32); and a chunk's boundary state [Hv, Dk, Dv] float32 written and
    read once a pass."""
    d = weights.dims(sizes)
    positions = chunks * d["chunk"]
    flops = 3.0 * positions * rule_forward_flops_per_position(sizes)
    item = 2  # bfloat16 activations
    row = (2 * d["Hk"] * d["Dk"] + d["Hv"] * d["Dv"]) * item + 2 * d["Hv"] * 4  # q, k, v, g, beta
    o = d["Hv"] * d["Dv"] * item
    state = d["Hv"] * d["Dk"] * d["Dv"] * 4
    return flops, positions * ((row + o) + (2 * row + o)) + chunks * 2 * 2 * state
