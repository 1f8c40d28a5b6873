"""Measure ledger overhead in StepClock phases (VERDICT r03 #7 done-check).

Runs the same synthetic federated config with the ledger off and on
(fingerprint mode — device-side digests) and reports the 'ledger' phase as a
fraction of total round wall. Acceptance: < 10% at small-bert x 10 clients.

Usage: python scripts/ledger_overhead.py [--model small-bert] [--clients 10]
           [--rounds 4] [--platform cpu] [--fused]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="small-bert")
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--fused", action="store_true",
                    help="also measure the fused (rounds_per_dispatch) path")
    args = ap.parse_args(argv)

    from bcfl_tpu.core.hostenv import compile_cache

    compile_cache()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from bcfl_tpu.config import FedConfig, LedgerConfig, PartitionConfig
    from bcfl_tpu.fed.engine import FedEngine

    def cfg(**kw):
        base = dict(
            dataset="synthetic", num_labels=2, seq_len=args.seq_len,
            batch_size=16, vocab_size=2048, model=args.model,
            num_clients=args.clients, num_rounds=args.rounds,
            max_local_batches=2, eval_every=0,
            partition=PartitionConfig(kind="iid", iid_samples=32))
        base.update(kw)
        return FedConfig(**base)

    rows = {}
    variants = {
        "no_ledger": cfg(),
        "ledger_fp": cfg(ledger=LedgerConfig(enabled=True)),
    }
    if args.fused:
        variants["ledger_fp_fused"] = cfg(
            ledger=LedgerConfig(enabled=True),
            rounds_per_dispatch=args.rounds)
    for name, c in variants.items():
        res = FedEngine(c).run()
        ph = res.metrics.phases
        total = sum(v["total_s"] for v in ph.values())
        ledger_s = ph.get("ledger", {}).get("total_s", 0.0)
        # the ledger phase nests inside round_program; don't double-count
        denom = max(total - ledger_s, 1e-9) if "ledger" in ph else total
        rows[name] = {
            "phases": {k: round(v["total_s"], 3) for k, v in ph.items()},
            "ledger_s": round(ledger_s, 3),
            "ledger_pct_of_wall": round(100.0 * ledger_s / denom, 2),
        }
        print(f"{name}: {rows[name]}", flush=True)

    out = {
        "model": args.model, "clients": args.clients, "rounds": args.rounds,
        "seq_len": args.seq_len, "rows": rows,
        "pass_lt_10pct": rows["ledger_fp"]["ledger_pct_of_wall"] < 10.0,
    }
    os.makedirs("results", exist_ok=True)
    with open("results/ledger_overhead.json", "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({"ledger_overhead_pct":
                      rows["ledger_fp"]["ledger_pct_of_wall"],
                      "pass": out["pass_lt_10pct"]}), flush=True)
    return 0 if out["pass_lt_10pct"] else 1


if __name__ == "__main__":
    sys.exit(main())
