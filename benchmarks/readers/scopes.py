"""Round programs (bcfl_tpu/fed/client_step.py): the device's busy time by
the programs' named scopes, from the traced bracket's table
(``ctx["trace"]["scopes"]``: milliseconds a round keyed by the scope itself,
the innermost ``fed.<name>`` of an operation's name stack, or
``transpose(fed.<name>)`` for its backward pass, and ``unscoped``;
``ctx["trace"]["op_names"]``: the same by whole name stack, for a look below
a scope or at every scope around an operation). Every metric
here counts its scope WITH the scopes nested in it, so a mechanism's
scope inside the model stays in the forward and backward time and has its
own key in the table too. No device trace (a CPU rehearsal): nothing to
read, None. A device trace whose operations carry no ``op_name``: an error
that says so, never 0."""

from benchmarks import trace_reduce as tr


def _trace(ctx):
    t = ctx["trace"]
    if not t:
        return None
    if t.get("scopes") is None:
        raise RuntimeError(t.get("scopes_error") or "the traced bracket has no scope table")
    return t


def _under(ctx, scope, backward=None, dropout=None, prefix=False):
    """Time of the operations under ``scope`` at any depth, the scopes a
    model nests in it with it (``fed.forward/Model/fed.moe.route`` counts
    under ``fed.forward``); ``prefix``: under any scope whose name starts so
    (``fed.optimizer`` with ``fed.optimizer_init``). ``backward`` picks one
    pass and ``dropout`` the operations below a flax ``Dropout_*`` module
    (True) or the others (False); None is either. None where the program has
    no such operation."""
    t = _trace(ctx)
    if t is None:
        return None
    hit = []
    for name, ms in t["op_names"].items():
        names, back = tr.scope_path(name)
        at = next((n for n in names if n == scope or (prefix and n.startswith(scope))), None)
        if at is None or backward not in (None, back):
            continue
        if dropout is None or ("Dropout_" in tr.below_scope(name, at)) == dropout:
            hit.append(ms)
    return sum(hit) if hit else None


def forward_ms_per_round(ctx):
    return _under(ctx, "fed.forward", backward=False, dropout=False)


def backward_ms_per_round(ctx):
    return _under(ctx, "fed.forward", backward=True, dropout=False)


def dropout_ms_per_round(ctx):
    """A lower bound: XLA keeps one ``op_name`` a fusion, and draws masks
    inside the forward and backward fusions where it can (PERF.md section 7)."""
    return _under(ctx, "fed.forward", dropout=True)


def optimizer_ms_per_round(ctx):
    return _under(ctx, "fed.optimizer", prefix=True)


def aggregate_ms_per_round(ctx):
    return _under(ctx, "fed.aggregate")
