#!/usr/bin/env python3
"""Readings that ``benchmarks/calibrate.py`` does not take for this family,
on the chip at the cell's own size (``--plumbing`` for the tiny preset), with
no engine built: the reference stands in the program's place.

``--fault``: this family's own four planted faults (``FAULTS`` of the
family: the rule's state not carried from one chunk to the next, the rule
without its erasing term, held expert 1's part left out, the attention's
output gate left out) against the sound reference, as calibrate.py reads its
two.
``--rates``: every local step's loss of round 0 at each learning rate, in the
stated precision (the look by which ``training.learning_rate`` was chosen).
Writes ``chiprun_out/readings-<cell>.json``."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--fault", action="store_true")
    ap.add_argument("--rates", default="", help="comma-separated learning rates")
    ap.add_argument("--plumbing", action="store_true")
    args = ap.parse_args()
    from benchmarks import compare, families, harness, traffic
    from benchmarks.reference import gate

    harness.place_compile_cache()
    cell, sizes = harness.load_cell(args.workload, args.plumbing)
    fam = families.of(sizes)
    program = fam.program(sizes)
    clients, n = cell["traffic"]["clients"], cell["check"]["rounds"]
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        batches, n_ex = traffic.make(cell["traffic"], program["vocab_size"], 2, seed,
                                     job=program["task"])
        mask = gate.expected_mask(cell.get("gate"), clients, seed).tolist()
        row = {"seed": seed}
        if args.fault:
            masks = [mask] * n
            recs = [{"mask": mask, "auth": [1.0] * clients, "train_loss": 0.0}] * n
            sound = fam.reference(sizes, seed, batches, masks, n_ex)
            stated = fam.reference(sizes, seed, batches, masks, n_ex,
                                   precision=fam.precisions(sizes)[0])["trained"]
            for name, fault in fam.FAULTS.items():
                r = fam.reference(sizes, seed, batches, masks, n_ex, fault=fault)
                v, notes = compare.numbers(
                    r["losses"], sound["losses"], r["trained"], sound["trained"], sound["start"],
                    sound["grad_norms"], recs, True, n * clients, clients, 0, stated=stated,
                    expected_mask=mask)
                row[name] = {k: x for k, x in v.items() if k.startswith(("loss_", "dparam_", "turn_"))}
                row[name]["worst_leaf"] = notes["dparam_worst_leaf"]
                harness.log(json.dumps({name: row[name]}))
        for rate in (float(x) for x in args.rates.split(",") if x):
            sz = dict(sizes, training=dict(sizes["training"], learning_rate=rate))
            r = fam.reference(sz, seed, batches, [mask], n_ex, precision=fam.precisions(sizes)[0])
            row[f"step_losses_at_{rate:g}"] = r["step_losses"]
            harness.log(json.dumps({f"step_losses_at_{rate:g}": r["step_losses"]}))
        row["seconds"] = time.time() - t0
        harness.log(json.dumps(row))
        out.append(row)
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", f"readings-{args.workload}.json"), "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
