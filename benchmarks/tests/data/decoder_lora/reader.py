"""A per-layer metric of a scope that no file of the benchmark names: the
adapters' merge into the frozen base (``fed.lora_merge``), forward and
backward, from the traced bracket's table."""


def merge_ms_per_round(ctx):
    t = ctx["trace"]
    if not t:
        return None
    if t.get("scopes") is None:
        raise RuntimeError(t.get("scopes_error") or "no scope table")
    hit = [ms for key, ms in t["scopes"].items()
           if key in ("fed.lora_merge", "transpose(fed.lora_merge)")]
    return sum(hit) if hit else None
