"""Engine layer (bcfl_tpu/fed/engine.py): the round's span tree. Each
reader takes one child of a phase from ``StepClock.summary()``
(``phases[parent]["children"][child]``) over the window's rounds. A program
without the span tree (no ``children`` under the phase) has nothing to read:
the reader returns None and the result line leaves the metric out."""


def _children(ctx, parent):
    ph = (ctx["phases"] or {}).get(parent) or {}
    return ph.get("children") or {}


def _child_ms_per_round(ctx, parent, child):
    span = _children(ctx, parent).get(child)
    if not span or not ctx["rounds"]:
        return None
    return 1e3 * span["total_s"] / ctx["rounds"]


def inputs_ms_per_round(ctx):
    return _child_ms_per_round(ctx, "round_program", "inputs")


def enqueue_ms_per_round(ctx):
    return _child_ms_per_round(ctx, "round_program", "enqueue")


def device_wait_ms_per_round(ctx):
    return _child_ms_per_round(ctx, "round_program", "wait")


def fetch_ms_per_round(ctx):
    return _child_ms_per_round(ctx, "round_program", "fetch")


def fingerprint_ms_per_round(ctx):
    return _child_ms_per_round(ctx, "ledger", "fingerprint")


def round_program_self_ms_per_round(ctx):
    """``round_program`` less its children (the nested ``ledger`` among
    them): what no child explains."""
    ph = (ctx["phases"] or {}).get("round_program") or {}
    if "self_s" not in ph or not ctx["rounds"]:
        return None
    return 1e3 * ph["self_s"] / ctx["rounds"]


def dispatches_per_round(ctx):
    """Calls into a round program (``round_program/enqueue`` spans) and
    into the ledger's fingerprint program, over the window's rounds."""
    enq = _children(ctx, "round_program").get("enqueue")
    if not enq or not ctx["rounds"]:
        return None
    fp = _children(ctx, "ledger").get("fingerprint") or {"count": 0}
    return (enq["count"] + fp["count"]) / ctx["rounds"]
