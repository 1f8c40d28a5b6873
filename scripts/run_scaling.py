"""Client-count scaling study: convergence vs federation size.

BASELINE.json's north-star metric is "samples/sec/chip + rounds-to-target
accuracy as the federation scales 4 -> 64 clients". This script measures the
convergence half on any host: serverless IID federated runs of the same model
over a geometric ladder of client counts, recording each count's global
accuracy-vs-round curve, the first round at which it crosses a fixed accuracy
threshold, and aggregate training throughput.

The per-client data budget is held constant (``--iid-samples`` per client per
round, the reference's resample-per-round schedule,
``src/Serverlesscase/serverless_IID_IMDB.py:258``), so scaling clients scales
the total per-round sample budget — the classic FL trade: more clients = more
data seen per round but a more averaged (less sequential) update.

On TPU each client is a mesh slot (one chip, or stacked clients per chip), so
wall-clock per round is ~flat as counts grow with the mesh; on this CPU host
the counts share one core, so wall-clock numbers here are NOT the scaling
story — rounds-to-threshold is. Emits ``<out>/scaling.json`` +
``<out>/scaling_curves.png`` and rewrites ``SCALING.md``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def first_crossing(curve, threshold):
    """1-based round index of the first curve point >= threshold, else None."""
    for i, a in enumerate(curve):
        if a >= threshold:
            return i + 1
    return None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--counts", type=int, nargs="*", default=[4, 16, 64])
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--model", default="tiny-bert")
    ap.add_argument("--dataset", default="medical_transcriptions")
    ap.add_argument("--num-labels", type=int, default=40)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--iid-samples", type=int, default=128,
                    help="per-client per-round sample budget (constant "
                    "across counts; total budget scales with the count)")
    ap.add_argument("--threshold", type=float, default=0.0,
                    help="accuracy whose first crossing is reported. 0 "
                    "(default) = RELATIVE mode: threshold is computed after "
                    "all runs as 0.9 x the SMALLEST count's final accuracy "
                    "— always reachable by construction and comparable "
                    "across counts (the r03 study's fixed 0.05 was 2x a "
                    "0.025 chance rate and measured noise)")
    ap.add_argument("--eval-batches", type=int, default=16)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--seed", type=int, default=None,
                    help="override FedConfig.seed (seed-repeat runs "
                    "quantify run-to-run noise for the trend claim)")
    ap.add_argument("--out", default="results")
    ap.add_argument("--no-md", action="store_true",
                    help="write <out>/scaling.json + curves but do NOT "
                    "rewrite SCALING.md (for fallback runs that must not "
                    "clobber a better run's table)")
    # --- registry axis (SCALING.md "Cohort mode") ---
    ap.add_argument("--registry-sizes", type=int, nargs="*", default=None,
                    help="run the COHORT sweep instead of the counts "
                    "ladder: one server-mode run per registry size, each "
                    "sampling --cohort-samples clients per round. Records "
                    "steady-state per-round wall per (registry, cohort) "
                    "point -> <out>/cohort_scaling.json. The claim under "
                    "test: wall scales with the sampled cohort, "
                    "sublinearly in registry size")
    ap.add_argument("--cohort-samples", type=int, nargs="*", default=[8],
                    help="sampled-cohort sizes for the registry sweep "
                    "(default: 8)")
    args = ap.parse_args(argv)

    # multi-client CPU meshes on a loaded host abort when a device thread
    # lags >40s behind the XLA collective rendezvous; raise the timeouts
    # BEFORE the backend initializes (same setup as run_results.py)
    from bcfl_tpu.core.hostenv import (
        compile_cache,
        raise_cpu_collective_timeouts,
    )

    raise_cpu_collective_timeouts()
    compile_cache()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from bcfl_tpu.config import FedConfig, PartitionConfig
    from bcfl_tpu.entrypoints.run import run
    from bcfl_tpu.viz.plots import accuracy_curves

    os.makedirs(args.out, exist_ok=True)
    if args.registry_sizes:
        return _registry_sweep(args, FedConfig, PartitionConfig, run)
    study = {}
    for count in args.counts:
        name = f"scale_{count}c"
        cfg = FedConfig(
            name=name, model=args.model, dataset=args.dataset,
            num_labels=args.num_labels, mode="serverless",
            weighted_agg=False, num_clients=count, num_rounds=args.rounds,
            seq_len=args.seq_len, max_eval_batches=args.eval_batches,
            partition=PartitionConfig(
                kind="iid", iid_samples=args.iid_samples,
                resample_each_round=True),
            **({"seed": args.seed} if args.seed is not None else {}),
        )
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        res = run(cfg, verbose=True)
        wall = time.time() - t0
        accs = res.metrics.global_accuracies
        samples = count * args.iid_samples * args.rounds
        study[count] = {
            "acc_curve": accs,
            "final_acc": accs[-1] if accs else None,
            "best_acc": max(accs) if accs else None,
            "train_samples_total": samples,
            "wall_minutes": wall / 60.0,
            "samples_per_sec_aggregate": samples / wall,
        }
        print(f"[{name}] best acc {study[count]['best_acc']}", flush=True)

    # threshold: explicit, or (relative mode) 0.9 x the smallest federation's
    # final accuracy — reachable by construction, so rounds-to-threshold is
    # defined for the anchor run and comparable across counts
    threshold = args.threshold
    rel = threshold <= 0.0
    if rel:
        anchor = min(study)
        threshold = round(0.9 * (study[anchor]["final_acc"] or 0.0), 4)
    for c, s in study.items():
        s["threshold"] = threshold
        s["rounds_to_threshold"] = first_crossing(s["acc_curve"], threshold)
        print(f"[scale_{c}c] rounds-to-{threshold}: "
              f"{s['rounds_to_threshold']}", flush=True)

    meta = {"model": args.model, "dataset": args.dataset,
            "num_labels": args.num_labels,
            "seq_len": args.seq_len, "iid_samples": args.iid_samples,
            "rounds": args.rounds, "threshold": threshold,
            "threshold_mode": ("0.9x smallest-count final" if rel
                               else "explicit"),
            "counts": args.counts}
    with open(os.path.join(args.out, "scaling.json"), "w") as f:
        json.dump({"meta": meta, "runs": study}, f, indent=2)
    accuracy_curves(
        {f"{c} clients": s["acc_curve"] for c, s in study.items()},
        title="Scaling: global accuracy vs round by client count",
        path=os.path.join(args.out, "scaling_curves.png"))
    if not args.no_md:
        _write_md(meta, study)
    print(f"\nwrote {args.out}/scaling.json"
          + ("" if args.no_md else " and SCALING.md"), flush=True)


def _registry_sweep(args, FedConfig, PartitionConfig, run):
    """Cohort-mode scaling sweep (SCALING.md "Cohort mode"): per-round wall
    time as a function of (registry_size, sampled cohort). The tentpole
    claim — per-round cost is bounded by the COHORT, sublinear in registry
    size — shows up as ~flat rows across registry sizes and growing columns
    across cohort sizes. Round 0 is excluded from the steady-state mean
    (it pays the program compiles)."""
    import numpy as np

    points = []
    for registry in args.registry_sizes:
        for cohort in args.cohort_samples:
            name = f"cohort_r{registry}_s{cohort}"
            cfg = FedConfig(
                name=name, model=args.model, dataset=args.dataset,
                num_labels=args.num_labels, mode="server",
                registry_size=registry, sample_clients=cohort,
                num_rounds=args.rounds, seq_len=args.seq_len,
                eval_every=0,
                partition=PartitionConfig(kind="iid",
                                          iid_samples=args.iid_samples),
                **({"seed": args.seed} if args.seed is not None else {}),
            )
            print(f"\n===== {name} =====", flush=True)
            res = run(cfg, verbose=True)
            walls = [r.wall_s for r in res.metrics.rounds]
            steady = walls[1:] or walls
            points.append({
                "registry_size": registry, "sample_clients": cohort,
                "round_wall_s": [round(w, 4) for w in walls],
                "steady_wall_s_mean": round(float(np.mean(steady)), 4),
                "final_train_loss": res.metrics.rounds[-1].train_loss,
            })
    path = os.path.join(args.out, "cohort_scaling.json")
    with open(path, "w") as f:
        json.dump({"meta": {"model": args.model, "dataset": args.dataset,
                            "rounds": args.rounds, "seq_len": args.seq_len,
                            "iid_samples": args.iid_samples,
                            "registry_sizes": args.registry_sizes,
                            "cohort_samples": args.cohort_samples},
                   "points": points}, f, indent=2)
    print(f"\n{'registry':>9} | {'cohort':>6} | steady wall s/round")
    print("-" * 40)
    for p in points:
        print(f"{p['registry_size']:>9} | {p['sample_clients']:>6} | "
              f"{p['steady_wall_s_mean']}")
    print(f"\nwrote {path}", flush=True)
    return 0


def _write_md(meta, study):
    lines = [
        "# SCALING — convergence vs federation size",
        "",
        "The north-star scaling metric (BASELINE.json): rounds-to-target "
        "accuracy as the federation grows 4 -> 64 clients, constant "
        "per-client data budget "
        f"({meta['iid_samples']} IID samples/client/round, resampled per "
        "round — the reference's schedule). Serverless mode, "
        f"`{meta['model']}` on `{meta['dataset']}`, seq_len "
        f"{meta['seq_len']}, {meta['rounds']} rounds.",
        "",
        "On TPU each client is a mesh slot, so wall-clock per round stays "
        "~flat as counts grow with the mesh (the multichip dryrun compiles "
        "exactly this program); on a CPU host all counts share the cores, "
        "so the scaling signal below is rounds-to-threshold and the "
        "curves, not wall-clock.",
        "",
        f"Threshold {meta['threshold']}"
        + (f" = {meta['threshold'] * meta['num_labels']:.1f}x the "
           f"1/{meta['num_labels']} chance rate"
           if meta.get("num_labels") else "")
        + f" ({meta.get('threshold_mode', 'explicit')}): reachable by "
        "construction for the smallest federation, so rounds-to-threshold "
        "is a defined, comparable quantity — not the r03 study's "
        "noise-level fixed cutoff.",
        "",
        f"| clients | best acc | final acc | rounds to {meta['threshold']} "
        "| total train samples | wall min |",
        "|---|---|---|---|---|---|",
    ]
    def fmt(v, spec):
        return format(v, spec) if v is not None else "—"

    for c, s in study.items():
        rt = s["rounds_to_threshold"]
        lines.append(
            f"| {c} | {fmt(s['best_acc'], '.3f')} | "
            f"{fmt(s['final_acc'], '.3f')} | "
            f"{rt if rt is not None else 'not reached'} | "
            f"{s['train_samples_total']} | {fmt(s['wall_minutes'], '.1f')} |")
    # derive the trend sentence, never assert it: emit only when the data
    # actually orders (more clients x more total data => fewer-or-equal
    # rounds to the shared threshold, strictly fewer at the extremes)
    cs = sorted(study)
    rts = [study[c]["rounds_to_threshold"] for c in cs]
    if (len(cs) >= 2 and all(r is not None for r in rts)
            and all(a >= b for a, b in zip(rts, rts[1:])) and rts[0] > rts[-1]):
        lines += [
            f"Measured trend: rounds-to-threshold falls monotonically "
            f"{rts[0]} -> {rts[-1]} as the federation grows "
            f"{cs[0]} -> {cs[-1]} clients at a constant per-client budget — "
            "larger federations see proportionally more data per round and "
            "converge in fewer rounds.",
            "",
        ]
    elif any(r is None for r in rts):
        lines += [
            "Note: some counts did not reach the threshold within the "
            "round budget; no scaling claim is made for them.",
            "",
        ]
    counts = " ".join(str(c) for c in meta.get("counts", []))
    lines += [
        "",
        "Curves: `results/scaling_curves.png`; raw data "
        "`results/scaling.json`. Reproduce this exact table: "
        f"`python scripts/run_scaling.py --counts {counts} "
        f"--model {meta['model']} --dataset {meta['dataset']} "
        + (f"--num-labels {meta['num_labels']} "
           if meta.get("num_labels") else "")
        + f"--rounds {meta['rounds']} --seq-len {meta['seq_len']} "
        f"--iid-samples {meta['iid_samples']} "
        f"--threshold {meta['threshold']}`.",
        "",
    ]
    with open("SCALING.md", "w") as f:
        f.write("\n".join(lines))


if __name__ == "__main__":
    main()
