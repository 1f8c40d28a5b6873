"""The Gated DeltaNet and gated attention decoder's layers
(bcfl_tpu/models/delta_moe.py): device time by the model's named scopes from
the traced bracket's table, the rule's counter from the
``round_program/records`` span's counts, and the rule's roofline share
(operations and bytes by the family's function,
benchmarks/families/delta_moe/flops.py, from the counter and the sizes).
Every scope metric counts its scope WITH what is nested in it, either pass,
by readers/scopes.py's own rule. A program without these scopes or the
counter (the parent of the PR that added them, another model): nothing to
read, None. No device trace: None. A device trace without ``op_name``: an
error."""

import sys

from benchmarks.readers import scopes


def mixer_ms_per_round(ctx):
    return scopes._under(ctx, "fed.gdn")


def rule_ms_per_round(ctx):
    return scopes._under(ctx, "fed.gdn.rule")


def gated_ms_per_round(ctx):
    return scopes._under(ctx, "fed.gattn")


def _counts(ctx):
    """The window's sums of the model's counters, or None without the rule's."""
    kids = (ctx["phases"].get("round_program") or {}).get("children") or {}
    c = kids.get("records") or {}
    return c if c.get("gdn_chunks") and ctx["rounds"] else None


def rule_roofline_pct(ctx):
    """Every operation under ``fed.gdn.rule``, whatever implements it,
    against the work the counted chunks require. The window's counters go to
    standard error beside it."""
    c = _counts(ctx)
    ms = rule_ms_per_round(ctx)
    if c is None or ms is None or ctx["platform"] != "tpu":
        return None
    print(f"[delta_moe] counters over the window's {ctx['rounds']} rounds: "
          + ", ".join(f"{k} {v:.0f}" for k, v in sorted(c.items()) if k != "count"),
          file=sys.stderr, flush=True)
    from benchmarks.families.delta_moe import flops

    flop, byts = flops.gated_delta_work(ctx["sizes"], c["gdn_chunks"] / ctx["rounds"])
    share = ctx["yardstick"].roofline(flop, byts, ms / 1e3, ctx["device_kind"])
    return None if share is None else share[0]
