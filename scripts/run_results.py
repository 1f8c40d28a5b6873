"""Real-data results runner.

Runs the Medical-Transcriptions experiments — the one reference dataset whose
data ships on disk (``/root/reference/Dataset/{train,test}_file_mt.csv``,
12,000/3,000 records, 40 specialties; SURVEY.md C20) — through the two preset
configurations whose published curves are BASELINE.md's Medical table:

- ``server_iid_medical``       (reference ``server_iid_medical_transcirptions.py``)
- ``serverless_noniid_medical``(reference ``Serverless_NonIID_Medical_transcriptions.py``)
- plus the BC-FL extension (ledger + PageRank gating + async) the reference
  only describes (README.md:10).

Emits per-run ``results/<name>.json`` + figures and rewrites ``RESULTS.md``
with the side-by-side against the reference's published numbers.

Usage:
    python scripts/run_results.py [--model small-bert] [--clients 10]
        [--rounds 20] [--platform cpu] [--hf] [--out results]

Zero-egress hosts cannot fetch the BioBERT checkpoint/tokenizer, so the
default is fresh-init + hash tokenizer (documented in RESULTS.md); on a host
with hub access pass ``--hf --model biobert-base`` for the
reference-faithful weights.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

REFERENCE = {  # BASELINE.md, Medical Transcriptions (BioBERT, 20 rounds)
    "server_iid_medical": {"final_acc": 0.68, "acc_10_workers": 0.672},
    "serverless_noniid_medical": {"final_acc": 0.736},
    "bcfl_async_pagerank_medical": {
        "info_sync_s": 28.96, "info_async_s": 3.62},  # BC-FL, PageRank filter
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="small-bert")
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=0,
                    help="0 = the preset default (128, the reference "
                    "configuration — always use this with --hf: WordPiece "
                    "expands medical terms ~1.5-2x, so short caps truncate "
                    "the tail). With the offline word-level hash tokenizer "
                    "the MT descriptions fit in 96 (p99 = 54 words), so "
                    "64-96 is a sound CPU-host speedup there only.")
    ap.add_argument("--iid-samples", type=int, default=0,
                    help="per-client IID draw per round for IID-partition "
                    "configs (0 = each preset's default, e.g. 500 for "
                    "server_iid_medical). Setting 400 matches the server "
                    "leg's per-round training data to the serverless leg's "
                    "contiguous 400-sample span on slow hosts; the value is "
                    "recorded in the summary row and disclosed in the "
                    "mode-ordering note. Non-IID configs are unaffected.")
    ap.add_argument("--eval-batches", type=int, default=0,
                    help="cap central eval batches per round (0 = full "
                    "3,000-row test split, the reference behaviour)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="evaluate every Nth round (per-round local+central "
                    "eval dominates wall on slow hosts; curves keep their "
                    "shape at every-2nd-round cadence)")
    ap.add_argument("--platform", default=None)
    ap.add_argument("--hf", action="store_true")
    ap.add_argument("--out", default="results")
    ap.add_argument("--configs", nargs="*", default=None,
                    help="subset of config names to run")
    ap.add_argument("--key-suffix", default="",
                    help="append to every summary key / artifact filename "
                    "(e.g. _smallbert) so a re-run at a different budget "
                    "accumulates NEXT TO earlier rows instead of "
                    "overwriting them; the mode-ordering note checks each "
                    "suffix's pair independently")
    ap.add_argument("--fresh", action="store_true",
                    help="start a new summary.json instead of merging into "
                    "an existing one (merging keeps stale entries from runs "
                    "with different flags)")
    ap.add_argument("--render-only", action="store_true",
                    help="skip training: re-render RESULTS.md + figures from "
                    "an existing <out>/summary.json (e.g. after patching "
                    "provenance fields into a summary produced by an older "
                    "version of this script)")
    args = ap.parse_args(argv)
    if args.eval_batches < 0:
        ap.error("--eval-batches must be >= 0")
    if args.eval_every < 1:
        ap.error("--eval-every must be >= 1")
    if args.seq_len < 0:
        ap.error("--seq-len must be >= 0")

    if args.render_only:
        # JSON + matplotlib only — no accelerator backend init (viz.plots
        # and bcfl_tpu/__init__ are import-light; render-only is exactly
        # the fallback for a host with no accelerator)
        from bcfl_tpu.viz.plots import accuracy_curves

        with open(os.path.join(args.out, "summary.json")) as f:
            summary = json.load(f)
        _render(args, summary, accuracy_curves)
        return

    from bcfl_tpu.core.hostenv import (
        compile_cache,
        raise_cpu_collective_timeouts,
    )

    raise_cpu_collective_timeouts()
    compile_cache()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from bcfl_tpu.config import LedgerConfig, PartitionConfig, TopologyConfig
    from bcfl_tpu.entrypoints.presets import get_preset
    from bcfl_tpu.entrypoints.run import run
    from bcfl_tpu.viz.plots import accuracy_curves

    os.makedirs(args.out, exist_ok=True)


    common = dict(model=args.model, num_clients=args.clients,
                  num_rounds=args.rounds, eval_every=args.eval_every,
                  max_eval_batches=args.eval_batches or None)
    if args.seq_len:
        common["seq_len"] = args.seq_len

    configs = {
        "server_iid_medical": get_preset(
            "server_iid_medical", hf=args.hf).replace(**common),
        "serverless_noniid_medical": get_preset(
            "serverless_noniid_medical", hf=args.hf).replace(**common),
        # the reference's serverless as it ACTUALLY executes (SURVEY §3.2):
        # clients train SEQUENTIALLY on one shared model object within each
        # round, then snapshots are averaged — i.e. ~num_clients x more
        # effective sequential optimization per round than independent
        # clients. If the reference's serverless>server accuracy gap rides
        # this quirk, this config reproduces it where the default
        # independent-clients serverless (above) measures a near-tie.
        "faithful_noniid_medical": get_preset(
            "serverless_noniid_medical", hf=args.hf).replace(
                **common, name="faithful_noniid_medical", faithful=True),
        # the BC-FL stack on the same data: hash-chained ledger payloads,
        # PageRank-gated aggregation, buffered-async rounds
        "bcfl_async_pagerank_medical": get_preset(
            "serverless_noniid_medical", hf=args.hf).replace(
                **common, sync="async",
                async_buffer=max(args.clients // 2, 1),
                topology=TopologyConfig(anomaly_filter="pagerank"),
                ledger=LedgerConfig(enabled=True)),
    }
    # augmentation study (SURVEY.md C20): the second real on-disk corpus —
    # self-driving sentiment, 500 rows — federated with and without the
    # reference's CTGAN augmentation file appended to the train split.
    # Small corpus => small federation: 4 clients x 100 IID samples/round.
    sdv_common = dict(common, num_clients=4)
    for aug in ("", "+ctgan"):
        key = "sdv_serverless_iid" + aug.replace("+", "_")
        configs[key] = get_preset(
            "serverless_covid_iid", hf=args.hf).replace(
                **sdv_common, name=key,
                dataset="self_driving_sentiment" + aug, num_labels=3,
                partition=PartitionConfig(
                    kind="iid", iid_samples=100, resample_each_round=True))
    if args.configs:
        configs = {k: v for k, v in configs.items() if k in args.configs}
    if args.iid_samples:
        # pin the TEST draw to the preset's effective value: iid_test_samples
        # defaults to iid_samples (partition.py:84), so overriding only the
        # train draw would silently shrink each client's local eval set too
        configs = {
            k: (cfg.replace(partition=dataclasses.replace(
                    cfg.partition, iid_samples=args.iid_samples,
                    iid_test_samples=(
                        cfg.partition.iid_test_samples
                        if cfg.partition.iid_test_samples is not None
                        else cfg.partition.iid_samples)))
                if cfg.partition.kind == "iid" else cfg)
            for k, cfg in configs.items()}

    import jax

    dev = jax.devices()[0]
    platform = f"{dev.platform} ({dev.device_kind}, {os.cpu_count()} host cores)"

    if args.key_suffix:
        configs = {name + args.key_suffix: cfg for name, cfg in configs.items()}

    summary = {}
    for name, cfg in configs.items():
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        res = run(cfg, verbose=True)
        wall = time.time() - t0
        m = res.metrics
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            f.write(m.to_json())
        accs = m.global_accuracies
        last = m.rounds[-1]
        summary[name] = {
            "model": args.model,
            "hf_weights": bool(args.hf),
            "clients": cfg.num_clients,
            "rounds": cfg.num_rounds,
            "seq_len": cfg.seq_len,
            "max_eval_batches": cfg.max_eval_batches,
            "eval_every": cfg.eval_every,
            "iid_samples": (cfg.partition.iid_samples
                            if cfg.partition.kind == "iid" else None),
            "dataset": cfg.dataset,
            "platform": platform,
            "final_acc": accs[-1] if accs else None,
            "best_acc": max(accs) if accs else None,
            "acc_curve": accs,
            # which (1-based) rounds the curve points came from — without
            # this a merged figure of different eval cadences would plot
            # incomparable x-indices as if they were the same rounds
            "acc_rounds": [r.round + 1 for r in m.rounds
                           if r.global_acc is not None],
            "model_size_gb": m.model_size_gb,
            "wall_minutes": wall / 60.0,
            "info_passing_sync_s": last.info_passing_sync_s,
            "info_passing_async_s": last.info_passing_async_s,
            "anomalies": last.anomalies,
            "ledger": m.ledger,
            "resources": m.resources,
        }
        print(f"[{name}] final acc "
              f"{summary[name]['final_acc']}, wall {wall/60:.1f} min",
              flush=True)

    # merge into any existing summary so partial runs (--configs subsets)
    # accumulate instead of clobbering earlier results (--fresh opts out)
    spath = os.path.join(args.out, "summary.json")
    if not args.fresh and os.path.exists(spath):
        with open(spath) as f:
            merged = json.load(f)
        merged.update(summary)
        summary = merged
    with open(spath, "w") as f:
        json.dump(summary, f, indent=2)
    print(f"\nwrote {spath}", flush=True)
    _render(args, summary, accuracy_curves)


def _render(args, summary, accuracy_curves):
    # label each curve with its eval cadence when sparser than every-round,
    # so a merged figure cannot pass off an every-2nd-round curve as
    # per-round progress
    def label(n, s):
        ee = s.get("eval_every") or 1
        return f"{n} (eval@{ee})" if ee > 1 else n

    curves = {label(n, s): s["acc_curve"]
              for n, s in summary.items() if s["acc_curve"]}
    if curves:
        accuracy_curves(
            curves, title="Real-data runs: global accuracy vs round",
            path=os.path.join(args.out, "medical_accuracy_curves.png"))
    _write_results_md(args, summary)
    print(f"wrote RESULTS.md (+figures in {args.out}/)", flush=True)


def _capacity_note(summary):
    """Derived (not asserted) model-capacity comparison: emitted only when
    the summary holds >= 2 distinct models AND the largest one actually
    scores best — stated as the measured fact it is. Entries are comparable
    only within one dataset at EQUAL round/seq_len/hf budgets (a merged
    summary can hold runs with different flags)."""
    by_key = {}
    for s in summary.values():
        if (s.get("model_size_gb") and s.get("best_acc") is not None
                and s.get("model") and s.get("dataset")):
            key = (s["dataset"], s.get("rounds"), s.get("seq_len"),
                   s.get("hf_weights"))
            by_key.setdefault(key, []).append(
                (s["model_size_gb"], s["best_acc"], s["model"]))
    # compare within ONE (dataset, budget) only (cross-task accuracy is
    # meaningless; cross-budget capacity claims conflate budget with size)
    sized = next((rows for rows in by_key.values()
                  if len({m for _, _, m in rows}) > 1), [])
    if not sized:
        return ""
    big, small = max(sized), min(sized)
    if big[1] <= small[1]:
        return ""
    return (f"Measured capacity effect: `{big[2]}` ({big[0]:.3f} GB) reaches "
            f"best acc {big[1]:.3f} vs `{small[2]}` ({small[0]:.3f} GB) "
            f"{small[1]:.3f} — model capacity, not the federation machinery, "
            "is what separates these offline fresh-init runs from the "
            "pretrained reference numbers.")


def _mode_ordering_note(summary, out_dir):
    """Derived (not asserted) serverless-vs-server ordering block: emitted
    only when both medical configs exist at the SAME (model, rounds,
    seq_len, clients, eval cap/cadence, hf) budget — the reference's
    headline claims are orderings (README.md:10: serverless −5% latency /
    +13% accuracy; MT nb cell 31: serverless-NonIID 73.6 vs server-IID 68
    final), so the honest offline check is whether the SIGNS reproduce at
    matched budgets. A merged summary can hold runs recorded under
    different flags; comparing those would conflate budget with mode."""
    # every --key-suffix re-run contributes its own pair; each is compared
    # only within its own suffix (matching budgets is checked per pair)
    def _matched(a, b):
        return a and b and not any(
            a.get(k) != b.get(k)
            for k in ("model", "rounds", "seq_len", "hf_weights",
                      "clients", "max_eval_batches", "eval_every")) \
            and a.get("final_acc") is not None \
            and b.get("final_acc") is not None

    pairs = []
    for key in sorted(summary):
        if not key.startswith("server_iid_medical"):
            continue
        suf = key[len("server_iid_medical"):]
        sv = summary.get("server_iid_medical" + suf)
        sl = summary.get("serverless_noniid_medical" + suf)
        if not _matched(sv, sl):
            continue
        fa = summary.get("faithful_noniid_medical" + suf)
        pairs.append((sv, sl, fa if _matched(sv, fa) else None))
    if not pairs:
        return ""
    lines = ["## Mode ordering vs the reference's headline claims", ""]
    for sv, sl, fa in pairs:
        lines += _pair_ordering_lines(sv, sl)
        if fa:
            lines += _faithful_lines(sv, sl, fa)
    lines += _worker_pair_lines(out_dir)
    lines.append("")
    return "\n".join(lines)


def _pair_ordering_lines(sv, sl):
    # the IID draw applies to the server leg only (the serverless leg's
    # contiguous Non-IID span is mode-intrinsic); disclose it when the
    # summary recorded one so a reduced-budget pair reads as such
    iid = (f", {sv['iid_samples']} IID samples/client/round (server leg)"
           if sv.get("iid_samples") else "")
    lines = [
        f"Matched budget ({sv['model']}, {sv['clients']} clients, "
        f"{sv['rounds']} rounds, seq {sv.get('seq_len')}{iid}):",
        "",
    ]
    acc_gap = sl["final_acc"] - sv["final_acc"]
    ref_line = ("reference: serverless-NonIID 0.736 vs server-IID 0.68 "
                "final (MT nb cell 31), README.md:10 claims +13%")
    sign = "REPRODUCES" if acc_gap > 0 else "does NOT reproduce"
    # point-wise lead count over the shared eval cadence: a final-round
    # ordering can hide the curve-level picture (e.g. serverless ahead at
    # every eval but the last) — derived only when the curves are actually
    # comparable (same eval rounds)
    leads = ""
    cv, cl = sv.get("acc_curve") or [], sl.get("acc_curve") or []
    rounds_match = (sv.get("acc_rounds") == sl.get("acc_rounds")
                    if sv.get("acc_rounds") or sl.get("acc_rounds")
                    # pre-acc_rounds summaries: the caller already matched
                    # rounds + eval_every, so equal-length curves share a
                    # cadence
                    else len(cv) == len(cl) and cv and cl)
    if rounds_match and len(cv) == len(cl) and cv:
        n_lead = sum(a > b for a, b in zip(cl, cv))
        leads = (f" Point-wise, serverless led at {n_lead} of "
                 f"{len(cv)} shared eval points.")
    lines.append(
        f"- **Accuracy**: serverless {sl['final_acc']:.3f} vs server "
        f"{sv['final_acc']:.3f} ({acc_gap:+.3f}) — the serverless>server "
        f"sign {sign} here ({ref_line}).{leads}")
    if sv.get("wall_minutes") and sl.get("wall_minutes"):
        lat_gap = sl["wall_minutes"] - sv["wall_minutes"]
        sign = "REPRODUCES" if lat_gap < 0 else "does NOT reproduce"
        lines.append(
            f"- **Latency**: serverless {sl['wall_minutes']:.1f} min vs "
            f"server {sv['wall_minutes']:.1f} min ({lat_gap:+.1f}) — the "
            f"serverless<server sign {sign} here (reference MT nb cell 15: "
            "105/122/187 vs 280/628/810 min).")
    lines.append("")
    return lines


def _faithful_lines(sv, sl, fa):
    """The reference's serverless AS IT EXECUTES (sequential-shared-model,
    SURVEY §3.2) vs this repo's independent-clients serverless, at the
    same matched budget — emitted only when the faithful config was run.
    Separates the reference's published serverless>server gap into
    'gossip averaging' vs 'the sequential quirk'."""
    gap_server = fa["final_acc"] - sv["final_acc"]
    gap_indep = fa["final_acc"] - sl["final_acc"]
    verdict = ("the reference's serverless>server accuracy gap REPRODUCES "
               "under its own sequential semantics"
               if gap_server > 0 else
               "even the sequential semantics do not beat server here")
    return [
        f"- **Faithful serverless** (the reference's sequential-shared-model "
        f"execution, SURVEY §3.2, same budget): {fa['final_acc']:.3f} vs "
        f"server {sv['final_acc']:.3f} ({gap_server:+.3f}) and vs "
        f"independent-clients serverless {sl['final_acc']:.3f} "
        f"({gap_indep:+.3f}) — {verdict}. Each faithful round trains "
        "clients sequentially on one shared model (~clients x more "
        "sequential optimization per round than independent clients).",
    ]


def _worker_pair_lines(out_dir):
    lines = []
    wp_path = os.path.join(out_dir, "worker_pair_smallbert.json")
    try:
        with open(wp_path) as f:
            wp = json.load(f)
        runs = wp.get("runs", {})
        if len(runs) >= 2:
            counts = sorted(runs, key=int)
            lo, hi = counts[0], counts[-1]
            a_lo, a_hi = runs[lo].get("final_acc"), runs[hi].get("final_acc")
            if a_lo is not None and a_hi is not None:
                trend = a_hi - a_lo
                sign = "rises" if trend > 0 else "is flat/falls"
                # the pair has its OWN budget (worker count is the variable
                # under test; its other knobs may differ from the rows
                # above) — state it so the numbers aren't attributed to the
                # header's budget
                lines.append(
                    f"- **Worker count** ({wp.get('model')}, serverless "
                    f"IID, its own budget: {wp.get('rounds')} rounds, seq "
                    f"{wp.get('seq_len')}, {wp.get('iid_samples')} "
                    f"samples/worker/round): {lo} workers {a_lo:.3f} -> "
                    f"{hi} workers {a_hi:.3f} ({trend:+.3f}) — accuracy "
                    f"{sign} with worker count (reference MT nb cell 18 "
                    "serverless: 0.75/0.758/0.775 for 5/10/20 — a +0.025 "
                    "spread; results/worker_pair_smallbert.json).")
    except (OSError, json.JSONDecodeError):
        pass
    return lines


def _write_results_md(args, summary):
    ref = REFERENCE
    # provenance comes from the recorded summary (authoritative, and correct
    # under --render-only where CLI args are just defaults); fall back to the
    # CLI for summaries written before these fields existed. Entries may
    # differ (the table carries per-row model/rounds), so the header prose
    # aggregates distinct values.
    any_s = next(iter(summary.values()), {})

    def distinct(key, fallback):
        vals = sorted({s.get(key) for s in summary.values()} - {None},
                      key=str)
        return "/".join(str(v) for v in vals) if vals else str(fallback)

    model = distinct("model", args.model)
    hf = any_s.get("hf_weights", args.hf)
    clients = distinct("clients", args.clients)
    rounds = distinct("rounds", args.rounds)
    lines = [
        "# RESULTS — real-data runs",
        "",
        "Datasets: the reference's on-disk CSVs (SURVEY.md C20) — "
        "Medical Transcriptions "
        "(`/root/reference/Dataset/train_file_mt.csv` 12,000 records / "
        "`test_file_mt.csv` 3,000 records, 40 medical specialties) and the "
        "self-driving sentiment corpus (500 records, 3 classes, plus its "
        "CTGAN/Copula/shuffle augmentation files). Loaded by "
        "`bcfl_tpu.data.datasets`, tokenized once, static-shape batches.",
        "",
    ]
    if not hf:
        lines += [
            "> **Weights caveat** — this host is zero-egress: the BioBERT "
            "checkpoint and WordPiece tokenizer cannot be fetched, so these "
            f"runs use fresh-initialized `{model}` with the hash "
            "tokenizer. Absolute accuracy is therefore NOT comparable to the "
            "reference's pretrained-BioBERT numbers; the comparison below is "
            "directional (mode ordering, learning curves, info-passing "
            "model). Re-run `python scripts/run_results.py --hf --model "
            "biobert-base` on a connected host for the weight-faithful "
            "experiment.",
            "",
        ]
    eval_cap = any_s.get("max_eval_batches")
    lines += [
        f"Configuration: {clients} clients x {rounds} rounds, "
        f"seq_len {distinct('seq_len', '?')} "
        f"(reference: 128), central eval "
        + (f"capped at {eval_cap} batches/round"
           if eval_cap else "on the full test split")
        + ", reference partition schedules (IID 500-random resampled/round "
        "for server; Non-IID contiguous 500i/400 with fixed test slice for "
        "serverless — SURVEY.md §2.1).",
        "",
        "| run | model (rounds) | final acc | best acc "
        "| reference (BioBERT) final | model GB "
        "| info sync s | info async s | wall min |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    def fmt(v, spec):
        return format(v, spec) if v is not None else "—"

    for name, s in summary.items():
        # suffixed keys (--key-suffix) still get their base config's
        # reference column: longest-prefix match over the REFERENCE names
        r = ref.get(name) or next(
            (ref[base] for base in sorted(ref, key=len, reverse=True)
             if name.startswith(base)), {})
        lines.append(
            f"| {name} | "
            f"{s.get('model', '?')} ({s.get('rounds', '?')}) | "
            f"{fmt(s.get('final_acc'), '.3f')} | "
            f"{fmt(s.get('best_acc'), '.3f')} | "
            f"{fmt(r.get('final_acc'), '')} | "
            f"{fmt(s.get('model_size_gb'), '.4f')} | "
            f"{fmt(s.get('info_passing_sync_s'), '.2f')} | "
            f"{fmt(s.get('info_passing_async_s'), '.2f')} | "
            f"{fmt(s.get('wall_minutes'), '.1f')} |")
    lines += [
        "",
        "Reference numbers: BASELINE.md (Medical table; notebook cells "
        "15/18/31 and the BC-FL cells 27-28).",
        "",
        _capacity_note(summary),
        "",
        (f"Wall-clock host: {any_s['platform']} — NOT a TPU perf number "
         "(that is `bench.py`/PERF.md)."
         if any_s.get("platform") else ""),
        # derive, don't assert: "still rising" = final point strictly above
        # every earlier point (a plateau or 1-point curve doesn't qualify)
        ("All curves are still rising at the final round (final acc strictly "
         "above every earlier round's), so final acc is a lower bound at "
         "this round budget."
         if summary and all(
             len(c := s.get("acc_curve") or []) > 1 and c[-1] > max(c[:-1])
             for s in summary.values()) else ""),
        "",
        "Figures: `results/medical_accuracy_curves.png` (+ per-run JSON in "
        "`results/`).",
        "",
    ]
    ordering = _mode_ordering_note(summary, args.out)
    if ordering:
        lines += [ordering, ""]
    def _any_key(prefix, exclude=None):
        # exact first, else any suffixed variant (--key-suffix runs);
        # `exclude` keeps a sibling config that extends the prefix (e.g.
        # sdv_serverless_iid_ctgan vs sdv_serverless_iid) from matching
        if prefix in summary:
            return summary[prefix]
        return next((summary[k] for k in sorted(summary)
                     if k.startswith(prefix)
                     and not (exclude and k.startswith(exclude))), None)

    bc = _any_key("bcfl_async_pagerank_medical")
    if bc:
        lines += [
            "## BC-FL extension (implemented, not just modeled)",
            "",
            "The reference's blockchain exists only as notebook markdown "
            "(SURVEY.md L6). Here the run above actually executes it: "
            "hash-chained per-(round, client) weight-digest ledger with "
            "authentication gating aggregation, PageRank anomaly gating "
            f"(anomalous nodes this run: {bc.get('anomalies', '—')}), "
            "buffered-async rounds, and ledger-payload info-passing "
            "accounting "
            f"(sync {fmt(bc.get('info_passing_sync_s'), '.2f')}s / async "
            f"{fmt(bc.get('info_passing_async_s'), '.2f')}s vs the "
            "reference's modeled 28.96s / 3.62s for the 0.043 GB payload "
            "class).",
            "",
        ]
    sdv = _any_key("sdv_serverless_iid", exclude="sdv_serverless_iid_ctgan")
    sdv_aug = _any_key("sdv_serverless_iid_ctgan")
    if sdv and sdv_aug:
        lines += [
            "## Synthetic-data augmentation on the self-driving corpus",
            "",
            "The reference ships CTGAN/GaussianCopula/random-shuffle "
            "augmentation files for its 500-row self-driving sentiment CSV "
            "but never trains on them (SURVEY.md C20). Here both runs are "
            "federated for real (serverless IID, 4 clients x "
            f"{sdv.get('rounds', '?')} rounds, 100 samples/client/round; "
            "the test split is always held out from the real rows): "
            f"plain corpus final acc {fmt(sdv.get('final_acc'), '.3f')} vs "
            "+CTGAN-augmented train split "
            f"{fmt(sdv_aug.get('final_acc'), '.3f')} "
            f"(best {fmt(sdv.get('best_acc'), '.3f')} vs "
            f"{fmt(sdv_aug.get('best_acc'), '.3f')}).",
            "",
        ]
    with open("RESULTS.md", "w") as f:
        f.write("\n".join(lines))


if __name__ == "__main__":
    main()
