"""The state-space and attention hybrid decoder under LoRA, one chip's share
(``bcfl_tpu/models/ssm_moe.py``): Mamba-2 mixers and attention without
positions in the published pattern, a routed expert layer of which the
configuration HOLDS a share, a shared MLP, a quarter of the vocabulary under a
tied head; a causal-LM job; adapters in float32 over a frozen bfloat16 base.
The interface is ``benchmarks/families/__init__.py``'s; the parts are beside
this file: ``weights.py`` (flat naming, a layer at a time from the seed, the
program's layout), ``plain.py`` (the plain reference: the recurrence a
position at a time, loss, layer-by-layer backward pass, AdamW, the mean, the
precisions), ``flops.py`` (the required operations; the scan's, the grouped
product's and attention's operations and bytes) and ``readings.py``.

The configuration names its cuts in keys of its own (``layers``,
``experts_held``, ``vocab_rows``); ``program`` maps them to the program's
registry name (``<model>@layers=..,experts_held=..``) and ``vocab_size``."""

from __future__ import annotations

from . import weights
from .flops import forward_flops_per_token, train_flops_per_token  # noqa: F401

make_weights = weights.make
to_program = weights.to_program
from_program = weights.from_program


def program(sizes):
    held = sizes["experts_held"]
    if not isinstance(held, int):
        raise ValueError("experts_held: a count n (experts 0 .. n-1); the program's name carries no list")
    model = f"{sizes['program_model']}@layers={sizes['layers']},experts_held={held}"
    # a program without this model stops here, before any weight is drawn
    from bcfl_tpu.models import get_config

    get_config(model)
    return {"model": model,
            "vocab_size": sizes["vocab_rows"], "num_labels": 2, "task": "causal_lm",
            "lora_rank": sizes["lora"]["r"], "remat": bool(sizes["training"].get("remat", True)),
            "use_flash": sizes["training"].get("use_flash")}


def precisions(sizes):
    p = sizes["training"]["reference_precisions"]
    return p["stated"], p["control"]


def reference(sizes, seed, batches, masks, n_ex, precision=None, fault=None):
    """``fault``: ``{"half_batch": True}``, ``{"drop_client": c}`` or this
    family's own ``{"drop_expert": e}`` (held expert e's part left out) and
    ``{"no_carry": True}`` (the recurrence's state not carried from one chunk
    to the next)."""
    from . import plain as ref

    return ref.run_rounds(sizes, seed, batches, masks, n_ex, precision=precision or "f32",
                          **(fault or {}))
