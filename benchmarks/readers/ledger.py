"""Ledger layer (bcfl_tpu/ledger): the host's chain work."""


def host_ms_per_round(ctx):
    ph = (ctx["phases"] or {}).get("ledger")
    if not ph or not ctx["rounds"]:
        return None
    return 1e3 * ph["total_s"] / ctx["rounds"]
