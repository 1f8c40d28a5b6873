"""The encoder family: BERT / ALBERT sequence classifiers, fine-tuned in
full (``bcfl_tpu/models/bert.py``). The interface is
``benchmarks/families/__init__.py``'s; the parts are beside this file:
``weights.py`` (flat naming, the program's layout), ``model.py`` (the plain
forward pass and loss, and the precisions' names), ``dropout.py`` (the
keep-masks from the seed), ``train.py`` (local AdamW steps and the weighted
mean) and ``flops.py`` (the required operations)."""

from __future__ import annotations

from . import weights
from .flops import forward_flops_per_token, train_flops_per_token  # noqa: F401

make_weights = weights.make
count = weights.count


def program(sizes):
    """The encoder configurations keep these three at the top level of
    their files, under the published names."""
    return {"model": sizes["program_model"], "vocab_size": sizes["vocab_size"],
            "num_labels": sizes["num_labels"]}


def to_program(flat, sizes):
    return weights.to_program(flat, sizes), None


def from_program(trainable, sizes):
    return weights.from_program(trainable, sizes)


def precisions(sizes):
    """``(stated, control)`` in ``model.py``'s names, from the
    configuration's file."""
    p = sizes["training"]["reference_precisions"]
    return p["stated"], p["control"]


def reference(sizes, seed, batches, masks, n_ex, precision=None, fault=None):
    """The whole model in float32 on the device (0.44 GB at BERT-base), one
    client and one step at a time."""
    import jax
    import jax.numpy as jnp

    from . import train

    start = weights.make(sizes, seed)
    losses, trained, gnorm = train.run_rounds(
        start, sizes, sizes["training"], jax.tree.map(jnp.asarray, batches), seed, masks,
        n_ex, precision=precision or "f32", **(fault or {}))
    return {"losses": [float(x) for x in losses], "trained": jax.device_get(trained),
            "start": jax.device_get(start), "grad_norms": jax.device_get(gnorm)}
