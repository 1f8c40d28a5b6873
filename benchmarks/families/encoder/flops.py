"""Required operations of the BERT / ALBERT sequence classifier, from the
configuration's shapes and never from the program; the rule is
``benchmarks/yardstick.py``'s."""

from __future__ import annotations

from benchmarks import yardstick


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


def train_flops_per_token(sizes, seq, cell=None):
    """Full fine-tuning: every matrix is trained, so every product has its
    forward, its activation-gradient and its weight-gradient product."""
    return yardstick.train_flops(forward_flops_per_token(sizes, seq), trained=True)
