"""The operations a trained token REQUIRES of the state-space and attention
hybrid decoder under LoRA, by ``yardstick.train_flops``'s rule, and the
operations and bytes of its three kernels for their roofline shares (the
state-space scan, the grouped expert product, causal attention): from the
configuration's sizes and the program's counters, the same whatever
implements them."""

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


def scan_forward_flops_per_position(sizes):
    """One position's forward operations in one mixer's recurrence, in the
    chunked form's causal products counted once: ``C_i . B_j`` and the
    masked product with ``dt x`` over the mean causal length of a chunk
    (``(chunk + 1) / 2`` positions), the entering state's ``S C_i`` and the
    state's own update ``dt x B^T``."""
    d = weights.dims(sizes)
    causal = (d["chunk"] + 1) / 2.0
    return (2 * causal * d["N"] + 2 * causal * d["Hm"] * d["P"]
            + 2 * 2 * d["Hm"] * d["P"] * d["N"])


def products(sizes, seq, cell=None):
    """``[(what, forward FLOP a position, trained?), ...]`` of one position's
    forward pass at the sizes held. A frozen matrix: its product, not
    trained (no weight-gradient product). An adapter's two factors: trained.
    Products of two activations (attention's two over the mean causal length
    ``(seq + 1) / 2``, the scan's): a gradient for each operand, as a trained
    one. A routed expert: the assignments that fall on held experts, in
    expectation ``k * G / E`` a REAL position (a padded one is routed to no
    expert: ``real_share``), frozen. The router: frozen. The depthwise
    convolution: frozen. The embedding is a gather and counts nothing; the
    tied head is its product."""
    d = weights.dims(sizes)
    r = d["r"]
    out = []
    held_per_token = d["k"] * d["G"] / d["E"] * real_share(cell)
    for kind in d["kinds"]:
        shapes = weights.matrix_shapes(sizes, kind)
        for name, (fi, fo) in shapes.items():
            group = ("router" if name == "r" else "shared MLP" if name in ("si", "so")
                     else "state-space mixer" if kind == "mamba" else "attention")
            out.append((group, 2 * fi * fo, False))
            if name != "r":
                out.append(("adapters", 2 * r * (fi + fo), True))
        if kind == "mamba":
            out.append(("state-space mixer", scan_forward_flops_per_position(sizes), True))
            out.append(("state-space mixer", 2 * d["K"] * (d["di"] + 2 * d["N"]), False))
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


def ssm_scan_work(sizes, chunks):
    """``(FLOP, bytes)`` the state-space scan REQUIRES for ``chunks`` chunks
    of ``mamba_chunk_size`` positions of a row (the program's counter
    ``ssm_scan_chunks``: rows x chunks a row, summed over client steps and
    layers), forward and backward: products of activations, so three times
    the forward operations (a gradient for each operand; the chunk states
    and masked products that a backward pass computes again count nothing).
    Bytes: ``x``, ``dt``, ``B`` and ``C`` read and ``y`` written once in the
    forward pass; the same four and ``dy`` read and their four gradients
    written in the backward pass, in the compute type (``dt`` float32); and a
    chunk's boundary state [heads, P, N] float32 written and read once a
    pass."""
    d = weights.dims(sizes)
    positions = chunks * d["chunk"]
    flops = 3.0 * positions * scan_forward_flops_per_position(sizes)
    item = 2  # bfloat16 activations
    row = (d["Hm"] * d["P"] + 2 * d["N"]) * item + d["Hm"] * 4  # x, B, C and dt of a position
    y = d["Hm"] * d["P"] * item
    state = d["Hm"] * d["P"] * d["N"] * 4
    return flops, positions * ((row + y) + (2 * row + y)) + chunks * 2 * 2 * state


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
    positions REQUIRES in one attention layer, forward and backward: the two
    products of activations, each with a gradient for each operand (six
    products of ``2 * heads * seq * (seq + 1) / 2 * head`` operations a row);
    q and o of every query head and k and v of every KEY-VALUE head read or
    written once a pass as in the latent-attention family's count (q, k, v
    read and o written forward; q, k, v, o, dO read and dq, dk, dv written
    backward), in the compute type."""
    d = weights.dims(sizes)
    flops = 6 * 2.0 * rows * d["heads"] * (seq * (seq + 1) / 2.0) * d["hd"]
    q_like, kv_like = d["heads"] * d["hd"], d["kv"] * d["hd"]
    elements = (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)
    return flops, rows * seq * elements * 2
