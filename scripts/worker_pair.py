"""Worker-count ordering at small-bert capacity: 5 vs 20 clients.

The reference's worker sweep shows accuracy rising with worker count
(``All_graphs_IMDB_dataset.ipynb`` cell 18: 76/83/88 for 5/10/20 — each
IID worker contributes its own 100-sample draw per round, so more workers
= more data per round). The r04 tiny-bert 5/10/20 sweep was FLAT
(``results/serverless_iid_medical_sweep.json`` 0.328/0.319/0.319) — but
tiny-bert also saturated ~0.37 on this corpus while small-bert reached
0.451 and was still climbing (RESULTS.md), i.e. the flatness is plausibly
a capacity ceiling, not a federation property. This runs the END POINTS
of the sweep (5 vs 20, the 4x data spread) at small-bert capacity, the
SAME per-worker budget for both counts (``--iid-samples`` can reduce it
below the preset's 500 to fit a slow host — more workers still means
proportionally more total data per round, the contrast under test; the
recorded JSON carries the value so RESULTS.md discloses it), to test
whether the reference's ordering appears once the model can absorb the
extra data.

Writes ``results/worker_pair_smallbert.json`` incrementally (the cheap
5-worker leg lands even if the 20-worker leg is cut short).

Usage: python scripts/worker_pair.py [--rounds 10] [--counts 5 20]
           [--iid-samples 250] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--counts", type=int, nargs="*", default=[5, 20])
    ap.add_argument("--model", default="small-bert")
    ap.add_argument("--seq-len", type=int, default=96)
    ap.add_argument("--eval-batches", type=int, default=24)
    ap.add_argument("--iid-samples", type=int, default=0,
                    help="per-worker IID draw per round (0 = the preset's "
                         "500, the reference budget). More workers = more "
                         "TOTAL data per round either way — the mechanism "
                         "the reference's rising worker trend rides — so a "
                         "reduced per-worker budget preserves the contrast "
                         "under test on a slow host; the recorded JSON "
                         "carries the value so RESULTS.md can disclose it")
    ap.add_argument("--platform", default=None)
    ap.add_argument("--out", default=os.path.join(REPO_ROOT, "results",
                                                  "worker_pair_smallbert.json"))
    args = ap.parse_args(argv)

    from bcfl_tpu.core.hostenv import (
        compile_cache,
        raise_cpu_collective_timeouts,
    )

    raise_cpu_collective_timeouts()
    compile_cache()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from bcfl_tpu.entrypoints.presets import get_preset
    from bcfl_tpu.entrypoints.run import run

    base = get_preset("serverless_iid_medical").replace(
        model=args.model, num_rounds=args.rounds, eval_every=2,
        max_eval_batches=args.eval_batches, seq_len=args.seq_len)
    if args.iid_samples:
        import dataclasses

        base = base.replace(partition=dataclasses.replace(
            base.partition, iid_samples=args.iid_samples))

    record = {"model": args.model, "rounds": args.rounds,
              "seq_len": args.seq_len, "dataset": base.dataset,
              "iid_samples": base.partition.iid_samples, "runs": {}}
    # resumable: a prior partial JSON (e.g. the cheap leg landed, the long
    # leg timed out) keeps its finished counts instead of re-paying them.
    # A budget-mismatched partial is preserved to a timestamped .bak —
    # those legs may be hours of compute and must never vanish silently.
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prev = json.load(f)
            if all(prev.get(k) == record[k] for k in
                   ("model", "rounds", "seq_len", "dataset", "iid_samples")):
                record["runs"] = prev.get("runs", {})
            elif prev.get("runs"):
                bak = f"{args.out}.bak{int(time.time())}"
                os.replace(args.out, bak)
                print(f"prior {args.out} was recorded under a different "
                      f"budget; preserved to {bak}", flush=True)
        except (OSError, json.JSONDecodeError, KeyError):
            pass
    for n in sorted(args.counts):  # cheap leg first: evidence lands early
        if str(n) in record["runs"]:
            print(f"[c{n}] already recorded, skipping", flush=True)
            continue
        cfg = base.replace(name=f"serverless_iid_medical_{args.model}_c{n}",
                           num_clients=n)
        t0 = time.time()
        res = run(cfg, verbose=True)
        accs = res.metrics.global_accuracies
        record["runs"][str(n)] = {
            "final_acc": accs[-1] if accs else None,
            "best_acc": max(accs) if accs else None,
            "acc_curve": [round(a, 4) for a in accs],
            "wall_min": round((time.time() - t0) / 60.0, 1),
        }
        with open(args.out, "w") as f:
            json.dump(record, f, indent=2)
        print(f"[c{n}] final {record['runs'][str(n)]['final_acc']} "
              f"-> {args.out}", flush=True)


if __name__ == "__main__":
    main()
