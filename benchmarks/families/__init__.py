"""Model families, found by name.

A configuration's file names its ``family``; the harness imports
``benchmarks/families/<family>`` and asks that package, and nothing else,
for whatever depends on the model. A later PR adds a family by adding such
a package (new files only) and edits nothing that is there. What a family's
``__init__.py`` has to define (``INTERFACE``; ``sizes`` is the
configuration's file as loaded, ``cell`` the cell's file):

``program(sizes)``
    The ``FedConfig`` fields that depend on the model, as one dict:
    ``model`` (the program's registry name), ``vocab_size``, ``num_labels``
    and, where they are not the defaults, ``task``, ``lora_rank``, ... The
    family maps them from whatever keys its configurations use, so a
    configuration may name its depth, its experts held or its vocabulary
    rows in keys of its own and list exactly those in ``reduced``. The
    traffic generator takes the vocabulary, the labels and the job kind
    (``task``) from the same dict.
``make_weights(sizes, seed)``
    Every array the program starts from (the base and what is trained, the
    adapters' start included), made on the device from the seed in the
    family's own flat naming and in the types the configuration states.
``to_program(flat, sizes)``
    ``(trainable, frozen)`` laid out as the program's trees; ``frozen`` is
    None for full fine-tuning.
``from_program(trainable, sizes)``
    The program's trained tree back in the flat naming: the TRAINED leaves
    only (under LoRA the adapters and any head trained in full).
``precisions(sizes)``
    ``(stated, control)``: the family's names for the precision the
    configuration states and for the nearest one below it.
``reference(sizes, seed, batches, masks, n_ex, precision=None, fault=None)``
    Follow the first ``len(masks)`` rounds from the seed under the rounds'
    real masks, in float32 at ``highest`` (``precision`` None) or in a named
    precision. ``batches`` are the traffic generator's numpy arrays
    [C, steps, B, ...]. Returns a dict of host values: ``losses`` (a round
    each), ``trained`` and ``start`` (flat dicts of the trained leaves after
    and before) and ``grad_norms`` (the first step's per-leaf gradient norm,
    the largest over the clients). The family makes its own weights from the
    seed and decides what it holds on the device at once (layer by layer
    where the model asks for it); the harness never holds the model.
    ``fault`` plants one fault the cell can have, for ``calibrate.py``:
    ``{"half_batch": True}`` or ``{"drop_client": c}``.
``forward_flops_per_token(sizes, seq)``, ``train_flops_per_token(sizes, seq, cell)``
    The operations the training result REQUIRES, by the rule in
    ``benchmarks/yardstick.py``.
"""

from __future__ import annotations

import importlib
import os

HERE = os.path.dirname(os.path.abspath(__file__))

INTERFACE = ("program", "make_weights", "to_program", "from_program", "precisions",
             "reference", "forward_flops_per_token", "train_flops_per_token")


def present():
    """The families that are there: the packages under this directory."""
    return sorted(d for d in os.listdir(HERE)
                  if os.path.exists(os.path.join(HERE, d, "__init__.py")))


def load(name):
    """The family's package. An unknown name is an error that names those
    present, and so is a package that lacks part of the interface."""
    if name not in present():
        raise KeyError(f"no model family {name!r} under benchmarks/families "
                       f"(it holds {present()})")
    fam = importlib.import_module(f"{__name__}.{name}")
    missing = [f for f in INTERFACE if not callable(getattr(fam, f, None))]
    if missing:
        raise AttributeError(f"family {name!r} lacks {missing} (see benchmarks/families/__init__.py)")
    return fam


def of(sizes):
    """The family of a configuration's file."""
    if "family" not in sizes:
        raise KeyError(f"configuration {sizes.get('name')!r} names no \"family\" "
                       f"(benchmarks/families holds {present()})")
    return load(sizes["family"])
