#!/usr/bin/env python3
"""Readings for the limits of ``correct`` (PERF.md, section 2), in one
process: for each seed the program's first rounds against the reference
(the lower readings), and on the first ``--controls`` seeds the control (the
reference in fp8, put in the program's place) and the faults the cell can
have, planted in the reference put in the program's place (the upper
readings); everything that depends on the model comes from the
configuration's family (benchmarks/families), found by its name; ``--control-only`` reads the control alone and builds no engine.
Not part of a benchmark run; run it on the chip at the cell's own size when
a limit has to be set, and with ``--plumbing`` for the tests' tiny sizes.
Writes ``chiprun_out/calibrate-<cell>.json``."""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def readings(cell_name, seeds, controls, plumbing, out_dir, control_only=False):
    import jax

    from benchmarks import compare, families, harness, traffic
    from benchmarks.reference import gate

    cell, sizes = harness.load_cell(cell_name, plumbing)
    fam = families.of(sizes)
    stated_p, control_p = fam.precisions(sizes)
    n = cell["check"]["rounds"]
    rows = []
    for i, seed in enumerate(seeds):
        t0 = time.time()
        run = harness.Run(cell, sizes, seed, 0.0, False, plumbing, out_dir, time.time())
        clients = cell["traffic"]["clients"]
        want_mask = gate.expected_mask(cell.get("gate"), clients, seed).tolist()
        if not control_only:
            harness.setup_engine(run)
            res, recs, _ = harness._drive(run, n)
            first = [harness._rec_dict(r) for r in recs]
            prog = fam.from_program(jax.device_get(res.trainable), sizes)
            del res
            harness.release(run)
        else:
            # the control's reading needs no program: the reference stands
            # in its place, under the mask a sound run has
            program = fam.program(sizes)
            run.batches, run.n_ex = traffic.make(
                cell["traffic"], program["vocab_size"], program.get("num_labels", 2), seed,
                job=program.get("task", "classification"))
            first = [{"mask": want_mask, "auth": [1.0] * clients, "train_loss": 0.0}] * n
        masks = [r["mask"] for r in first]

        def ref(**kw):
            return fam.reference(sizes, seed, run.batches, masks, run.n_ex, **kw)

        def against(losses, params):
            v, notes = compare.numbers(losses, sound["losses"], params, sound["trained"],
                                       sound["start"], sound["grad_norms"], first, True,
                                       len(first) * clients, clients, 0, stated=stated,
                                       expected_mask=want_mask)
            v = {k: x for k, x in v.items() if k.startswith(("loss_", "dparam_", "turn_"))}
            v["worst_leaf"] = notes["dparam_worst_leaf"]
            return v

        sound = ref()
        stated = ref(precision=stated_p)["trained"]
        row = {"seed": seed, "masks": masks}
        if not control_only:
            row["program"] = against([r["train_loss"] for r in first], prog)
            row["correct"] = compare.judge(
                {k: v for k, v in row["program"].items() if k != "worst_leaf"},
                {k: v for k, v in cell["limits"].items() if k in row["program"]})[1]
        if i < controls:
            def read(**kw):
                r = ref(**kw)
                return against(r["losses"], r["trained"])

            row["control"] = read(precision=control_p)
            if not control_only:
                row["fault_half_batch"] = read(fault={"half_batch": True})
                live = [c for c, m in enumerate(masks[0]) if m > 0]
                if len(live) > 1:
                    row["fault_client_left_out"] = read(fault={"drop_client": live[-1]})
        row["seconds"] = time.time() - t0
        rows.append(row)
        harness.log(json.dumps(row))
        # every row as it comes: a call that is cut keeps what it read
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", f"calibrate-{cell_name}.rows.jsonl"), "a") as f:
            f.write(json.dumps(row) + "\n")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--control-only", action="store_true",
                    help="the control's readings alone: no engine is built and no fault planted")
    ap.add_argument("--plumbing", action="store_true")
    args = ap.parse_args()
    from benchmarks import harness

    harness.place_compile_cache()
    import jax

    out_dir = os.path.join(ROOT, "bench_out", args.workload + ".calibrate")
    os.makedirs(out_dir, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = readings(args.workload, seeds, args.controls, args.plumbing, out_dir,
                    control_only=args.control_only)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"calibrate-{args.workload}"
                        f"{'-plumbing' if args.plumbing else ''}.json")
    with open(path, "w") as f:
        json.dump({"workload": args.workload, "device": str(jax.devices()[0].device_kind),
                   "plumbing": args.plumbing, "rows": rows}, f, indent=1)
    # the summary: largest program reading, smallest control / fault reading
    kinds = ("program", "control", "fault_half_batch", "fault_client_left_out")
    keys = sorted({k for r in rows for kind in kinds for k in r.get(kind, {}) if k != "worst_leaf"})
    for k in keys:
        line = k + ":"
        vals = [r["program"][k] for r in rows if "program" in r]
        if vals:
            line += f" program max {max(vals):.3g}"
        for kind in kinds[1:]:
            vals = [r[kind][k] for r in rows if kind in r]
            if vals:
                line += f" | {kind} min {min(vals):.3g}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
