"""The latent-attention expert decoder's layers (bcfl_tpu/models/latent_moe.py):
device time by the model's named scopes from the traced bracket's table, the
expert layer's counters from the ``round_program/records`` span's counts, and
the two kernels' roofline shares (operations and bytes by the family's
functions, benchmarks/families/latent_moe/flops.py, from the counters and the
sizes). Every scope metric counts its scope WITH what is nested in it, either
pass, by readers/scopes.py's own rule. A program without these scopes or counters
(the parent of the PR that added them, another model): nothing to read, None.
No device trace: None. A device trace without ``op_name``: an error."""

import re

from benchmarks import trace_reduce as tr
from benchmarks.readers import scopes

# the operations that ARE a grouped product / a flash kernel, by what follows
# the scope in the name stack: a Pallas call (megablox's gmm, the flash
# kernels) or XLA's ragged dot
_GROUPED = re.compile(r"pallas_call|gmm|ragged_dot")
_FLASH = re.compile(r"pallas_call|_fwd_kernel|_dkv_kernel|_dq_kernel|flash")


def _kernel_ms(ctx, scope, kernel):
    """ms a round of the operations under ``scope`` whose name below it
    matches ``kernel``."""
    t = scopes._trace(ctx)
    if t is None:
        return None
    hit = [ms for name, ms in t["op_names"].items()
           if scope in tr.scope_path(name)[0] and kernel.search(tr.below_scope(name, scope))]
    return sum(hit) if hit else None


def experts_ms_per_round(ctx):
    return scopes._under(ctx, "fed.moe.experts")


def route_ms_per_round(ctx):
    return scopes._under(ctx, "fed.moe.route")


def mla_ms_per_round(ctx):
    return scopes._under(ctx, "fed.mla")


def lora_ms_per_round(ctx):
    return scopes._under(ctx, "fed.lora")


def lm_head_ms_per_round(ctx):
    return scopes._under(ctx, "fed.lm_head")


def _counts(ctx):
    """The window's sums of the expert layer's counters, or None."""
    kids = (ctx["phases"].get("round_program") or {}).get("children") or {}
    c = kids.get("records") or {}
    return c if "moe_slots_held" in c else None


def held_share_pct(ctx):
    c = _counts(ctx)
    if c is None:
        return None
    total = c["moe_slots_held"] + c.get("moe_slots_absent", 0)
    return 100.0 * c["moe_slots_held"] / total if total else None


def _client_steps_layers(ctx):
    t = ctx["cell"]["traffic"]
    return t["clients"] * t["local_batches"] * ctx["sizes"]["layers"]


def rows_max_over_mean(ctx):
    c = _counts(ctx)
    if c is None or not c["moe_slots_held"] or not ctx["rounds"]:
        return None
    from benchmarks.families.latent_moe import weights

    mean = (c["moe_slots_held"] / ctx["rounds"] / _client_steps_layers(ctx)
            / weights.dims(ctx["sizes"])["G"])
    return (c.get("moe_rows_max", 0) / ctx["rounds"]) / mean


def _roofline(ctx, flops, bytes_moved, ms):
    if ms is None or ctx["platform"] != "tpu":
        return None
    share = ctx["yardstick"].roofline(flops, bytes_moved, ms / 1e3, ctx["device_kind"])
    return None if share is None else share[0]


def grouped_matmul_roofline_pct(ctx):
    c = _counts(ctx)
    ms = _kernel_ms(ctx, "fed.moe.experts", _GROUPED)
    if c is None or ms is None or not ctx["rounds"]:
        return None
    from benchmarks.families.latent_moe import flops

    t = ctx["cell"]["traffic"]
    # the clients fold into the rows: a step's grouped product reads the held
    # experts' weights once for all of them
    flop, byts = flops.grouped_matmul_work(
        ctx["sizes"], c["moe_slots_held"] / ctx["rounds"],
        t["local_batches"] * ctx["sizes"]["layers"])
    return _roofline(ctx, flop, byts, ms)


def flash_attention_roofline_pct(ctx):
    ms = _kernel_ms(ctx, "fed.mla", _FLASH)
    if ms is None:
        return None
    from benchmarks.families.latent_moe import flops

    t = ctx["cell"]["traffic"]
    flop, byts = flops.flash_attention_work(ctx["sizes"], t["seq"], t["batch"])
    n = _client_steps_layers(ctx)
    return _roofline(ctx, n * flop, n * byts, ms)
