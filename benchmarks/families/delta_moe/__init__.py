"""The Gated DeltaNet and gated attention decoder with experts under LoRA,
one chip's share (``bcfl_tpu/models/delta_moe.py``): Gated DeltaNet mixers and
gated full attention in the published pattern, a routed expert layer of which
the configuration HOLDS a share, a gated shared expert, an eighth of the
vocabulary under an untied head; a causal-LM job; adapters in float32 over a
frozen bfloat16 base. The interface is ``benchmarks/families/__init__.py``'s;
the parts are beside this file: ``weights.py`` (flat naming, a layer at a
time from the seed, the program's layout), ``plain.py`` (the plain reference:
the rule a position at a time, loss, layer-by-layer backward pass, AdamW,
the mean, the precisions), ``flops.py`` (the required operations; the rule's
operations and bytes) and ``readings.py``.

The configuration names its cuts in keys of its own (``layers``,
``experts_held``, ``vocab_rows``); ``program`` maps them to the program's
registry name (``<model>@layers=..,experts_held=..``) and ``vocab_size``."""

from __future__ import annotations

from . import weights
from .flops import forward_flops_per_token, train_flops_per_token  # noqa: F401

make_weights = weights.make
to_program = weights.to_program
from_program = weights.from_program

#: this family's own planted faults (``plain.run_rounds``), for readings.py
#: and the tests: the rule's state not carried from one chunk to the next,
#: the rule without its erasing term, one held expert's part left out, the
#: attention's output gate left out
FAULTS = {"no_carry": {"no_carry": True}, "no_erase": {"no_erase": True},
          "drop_expert": {"drop_expert": 1}, "no_gate": {"no_gate": True}}


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
    """``fault``: ``{"half_batch": True}``, ``{"drop_client": c}`` or one of
    this family's own (:data:`FAULTS`)."""
    from . import plain as ref

    return ref.run_rounds(sizes, seed, batches, masks, n_ex, precision=precision or "f32",
                          **(fault or {}))
