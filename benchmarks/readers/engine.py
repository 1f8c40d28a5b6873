"""Engine layer (bcfl_tpu/fed/engine.py): host spans and path counters."""

import sys

import numpy as np


def host_ms_per_round(ctx):
    """StepClock: every phase but ``round_program``, over the window's rounds."""
    phases = ctx["phases"] or {}
    host = [v["total_s"] for k, v in phases.items() if k != "round_program"]
    if not host or not ctx["rounds"]:
        return None
    return 1e3 * sum(host) / ctx["rounds"]


def fused_round_pct(ctx):
    recs = ctx["records"]
    if not recs:
        return None
    return 100.0 * sum(1 for r in recs if r["fused"]) / len(recs)


def round_ms_p95(ctx):
    walls = [r["wall_s"] for r in ctx["records"]]
    if len(walls) < 10:
        return None
    print(f"[bench] engine.round_ms_p95 over {len(walls)} rounds", file=sys.stderr)
    return 1e3 * float(np.percentile(walls, 95))
