"""The comparison that decides ``correct``: each number beside its limit.

What is compared is what the timed path itself produced in its first rounds,
driven from the seed by the same engine object that the window then uses:
every round's training loss, the change of the global parameters, and the
ledger's verdicts, against the plain reference (benchmarks/reference) held to
the masks those rounds really had; and every round's mask itself against the
gate's plain recomputation from the seed. ``--trace`` plays no part here: the
compared rounds ran before the window and before any profiler."""

from __future__ import annotations

import numpy as np

ZERO_GRAD_SHARE = 1e-3  # a leaf whose reference gradient is under this share
# of the median leaf's moves under Adam by round-off alone: not compared


def _norm(x):
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def change_gaps(prog, ref, start, ref_grad_norm):
    """Per leaf, the gap between the norm of the program's change and the
    norm of the reference's, measured against the reference's norm of that
    leaf or of the median leaf, whichever is larger: the ``worst`` gap, its
    ``leaf``, the ``median`` gap and the leaves ``left_out``. Also ``turn``,
    1 - cosine between the program's whole change and the reference's, and
    ``diff_median``, the median leaf's norm of the difference of the two
    changes over the reference's norm."""
    rows = []
    for name in ref:
        p0 = np.asarray(start[name], np.float64)
        a = np.asarray(prog[name], np.float64) - p0
        b = np.asarray(ref[name], np.float64) - p0
        rows.append((name, _norm(a), _norm(b), float(ref_grad_norm[name]),
                     float(np.sum(a * b)), _norm(a - b)))
    gmed = float(np.median([r[3] for r in rows]))
    kept = [r for r in rows if r[3] >= ZERO_GRAD_SHARE * gmed]
    left_out = [r[0] for r in rows if r[3] < ZERO_GRAD_SHARE * gmed]
    med = float(np.median([r[2] for r in kept]))
    gaps = [(abs(dp - dr) / max(dr, med, 1e-30), name) for name, dp, dr, *_ in kept]
    worst, leaf = max(gaps)
    # the direction of the whole change (all compared leaves as one vector):
    # Adam's step keeps its norm whatever rounding does to a gradient's
    # small entries, but not its direction
    dot = sum(r[4] for r in kept)
    na = np.sqrt(sum(r[1] ** 2 for r in kept))
    nb = np.sqrt(sum(r[2] ** 2 for r in kept))
    turn = 1.0 - dot / max(na * nb, 1e-300)
    diff_med = float(np.median([r[5] / max(r[2], med, 1e-30) for r in kept]))
    return {"worst": worst, "leaf": leaf, "median": float(np.median([g for g, _ in gaps])),
            "left_out": left_out, "turn": turn, "diff_median": diff_med}


def numbers(prog_losses, ref_losses, prog, ref, start, ref_grad_norm, records,
            chain_ok, chain_len, clients, compiles_in_window, stated=None,
            expected_mask=None):
    """``{name: value}`` of everything compared, in a fixed order. ``stated``
    is the reference's own result in the precision the configuration states
    (bfloat16): how far rounding at that precision turns the change differs
    several-fold from seed to seed, so the program's turn is measured
    against it (``turn_vs_stated``) and not against a fixed number.
    ``expected_mask`` is the participation mask that the plain recomputation
    of the gate (reference/gate.py) gives every round of a sound run."""
    out = {}
    for r, (a, b) in enumerate(zip(prog_losses, ref_losses)):
        out[f"loss_r{r}"] = abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)
    g = change_gaps(prog, ref, start, ref_grad_norm)
    out["dparam_worst"] = g["worst"]
    out["dparam_median"] = g["median"]
    out["dparam_turn"] = g["turn"]
    out["dparam_diff_median"] = g["diff_median"]
    if stated is not None:
        turn_stated = change_gaps(stated, ref, start, ref_grad_norm)["turn"]
        out["dparam_turn_stated"] = turn_stated
        out["turn_vs_stated"] = g["turn"] / max(turn_stated, 1e-12)
    auth_bad = sum(1 for rec in records
                   if rec.get("auth") is None or any(a != 1.0 for a in rec["auth"]))
    nonfinite = sum(1 for rec in records if not np.isfinite(rec["train_loss"]))
    if expected_mask is not None:
        want = np.asarray(expected_mask, np.float64)
        out["mask_mismatch_rounds"] = float(sum(
            1 for rec in records
            if rec.get("mask") is None
            or not np.array_equal(np.asarray(rec["mask"], np.float64), want)))
    out["auth_failed_rounds"] = float(auth_bad)
    out["nonfinite_rounds"] = float(nonfinite)
    out["chain_broken"] = 0.0 if chain_ok else 1.0
    out["chain_missing_entries"] = float(abs(chain_len - len(records) * clients))
    out["compiles_in_window"] = float(compiles_in_window)
    notes = {"dparam_worst_leaf": g["leaf"], "leaves_left_out": g["left_out"]}
    return out, notes


def judge(values, limits):
    """``[(name, value, limit, ok), ...]`` and whether all hold. A number
    with no limit in the cell's file is printed and not judged; a limit with
    no number fails."""
    rows, ok = [], True
    for name, v in values.items():
        lim = limits.get(name)
        good = True if lim is None else bool(np.isfinite(v) and v <= lim)
        ok = ok and good
        rows.append((name, v, lim, good))
    for name in limits:
        if name not in values:
            rows.append((name, None, limits[name], False))
            ok = False
    return rows, ok
