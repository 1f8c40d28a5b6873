"""Measure the LoRA adapter-exchange communication win, end to end.

The adapter is the unit of federated exchange whenever ``lora_rank > 0``:
the engine's trainable tree IS the adapter tree, so the codecs, the ledger
fingerprints, the bytes-on-wire accounting, and the dist runtime's update/
broadcast frames all operate on adapter payloads with no extra plumbing.
This script proves and measures that claim (COMPRESSION.md "Adapter
exchange"), artifact-gated like ``scripts/comm_overhead.py``: writes
``results/lora_comm.json`` with the acceptance flags.

Legs and gates:

1. **Local A/B** — the same synthetic federated config full-fine-tune vs
   adapter exchange. Gates: >= ``--min-reduction`` (default 50) x fewer
   bytes-on-wire per round, AND matched final loss — the adapter run's
   final train loss must be within ``--loss-tol`` of the full run's
   (default 0.05 ABSOLUTE on the CE loss; both runs train the task head in
   full — HF modules_to_save convention — so on this task the tolerance is
   a parity check, not a handicap).
2. **Stacked codecs** — the adapter run re-measured under int8+topk: the
   codec ratio MULTIPLIES the adapter ratio (recorded, not gated — the
   codec's own gates live in comm_overhead.py).
3. **Heterogeneous ranks** — one fleet at ``--lora-ranks`` (>= 2 distinct
   ranks) under the rank-aware RBLA aggregator. Gates: the run completes,
   every round records an effective-rank statistic (the rank-collapse
   guard), and the round program compiled EXACTLY once (zero per-round
   retraces — the padding mask is a static function of the rank spec).
4. **Dist loopback** — a real ``--peers``-process run with adapters on the
   wire, and its full-model twin for the denominator. Gates: max measured
   update frame <= ``--frame-cap`` (default 2%) of the full-model run's
   max update frame, and ZERO telemetry-invariant violations over the
   adapter run's event streams.

Usage: python scripts/lora_comm.py [--model tiny-bert] [--platform cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny-bert")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=8192,
                    help="synthetic vocab for the local A/B — sets the "
                         "full-model denominator (embeddings ship in the "
                         "full exchange but are frozen under LoRA)")
    ap.add_argument("--lora-rank", type=int, default=2,
                    help="uniform adapter rank for the A/B and dist legs "
                         "(rank 2 on tiny-bert is the documented >= 50x "
                         "point; higher ranks trade bytes for capacity)")
    ap.add_argument("--lora-ranks", default="2,4",
                    help="heterogeneous spec for leg 3 (>= 2 distinct "
                         "ranks, cycled over clients)")
    ap.add_argument("--loss-tol", type=float, default=0.05,
                    help="adapter final loss must be <= full final loss + "
                         "this (absolute CE tolerance — the matched-loss "
                         "definition for the bytes gate)")
    ap.add_argument("--min-reduction", type=float, default=50.0)
    ap.add_argument("--frame-cap", type=float, default=0.02,
                    help="max adapter update frame as a fraction of the "
                         "full-model run's max update frame")
    ap.add_argument("--peers", type=int, default=3)
    ap.add_argument("--dist-clients", type=int, default=6)
    ap.add_argument("--dist-rounds", type=int, default=3)
    ap.add_argument("--dist-deadline", type=float, default=300.0)
    ap.add_argument("--platform", default=None)
    ap.add_argument("--out", default="results/lora_comm.json")
    args = ap.parse_args(argv)

    from bcfl_tpu.core.hostenv import compile_cache

    compile_cache()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    from bcfl_tpu.compression import CompressionConfig
    from bcfl_tpu.config import (
        DistConfig,
        FedConfig,
        LedgerConfig,
        PartitionConfig,
    )
    from bcfl_tpu.fed.engine import FedEngine

    def cfg(name, **kw):
        return FedConfig(
            name=name, dataset="synthetic", num_labels=2,
            seq_len=args.seq_len, batch_size=16, vocab_size=args.vocab,
            model=args.model, num_clients=args.clients,
            num_rounds=args.rounds, max_local_batches=2,
            learning_rate=3e-4, eval_every=0,
            partition=PartitionConfig(kind="iid", iid_samples=32), **kw)

    def run(c):
        res = FedEngine(c).run()
        recs = res.metrics.rounds
        return {
            "bytes_on_wire_per_round": recs[0].bytes_on_wire,
            "final_train_loss": round(recs[-1].train_loss, 5),
            "effective_rank": [r.effective_rank for r in recs],
        }

    # ---- leg 1: local A/B (full-model vs adapter exchange) ----
    full = run(cfg("lora_comm_full"))
    adapter = run(cfg("lora_comm_adapter", lora_rank=args.lora_rank))
    reduction = (full["bytes_on_wire_per_round"]
                 / max(adapter["bytes_on_wire_per_round"], 1))
    loss_delta = adapter["final_train_loss"] - full["final_train_loss"]
    print(f"A/B: full={full['bytes_on_wire_per_round']:.0f} B/round, "
          f"adapter={adapter['bytes_on_wire_per_round']:.0f} B/round "
          f"({reduction:.1f}x), loss delta={loss_delta:+.5f}", flush=True)

    # ---- leg 2: stacked codec ratio (adapter deltas through int8+topk) ----
    stacked = run(cfg("lora_comm_stacked", lora_rank=args.lora_rank,
                      compression=CompressionConfig(kind="int8+topk")))
    stacked_x = (full["bytes_on_wire_per_round"]
                 / max(stacked["bytes_on_wire_per_round"], 1))
    print(f"stacked int8+topk: "
          f"{stacked['bytes_on_wire_per_round']:.0f} B/round "
          f"({stacked_x:.1f}x vs full uncompressed)", flush=True)

    # ---- leg 3: heterogeneous ranks under the RBLA aggregator ----
    het_cfg = cfg("lora_comm_hetero", lora_ranks=args.lora_ranks)
    het_eng = FedEngine(het_cfg)
    het_res = het_eng.run()
    het_recs = het_res.metrics.rounds
    het_eff = [r.effective_rank for r in het_recs]
    # the per-round program compiled exactly once: the [C, R] padding mask
    # is a closure constant of the static rank spec, so WHICH client holds
    # WHICH rank never retraces (same pin as scripts/chaos_smoke.sh)
    het_retraces = int(het_eng.progs.server_round._cache_size())
    print(f"hetero ranks={het_cfg.client_lora_ranks}: effective_rank="
          f"{[round(e, 3) for e in het_eff]}, "
          f"server_round cache entries={het_retraces}", flush=True)

    # ---- leg 4: dist loopback — adapters on the real wire ----
    from bcfl_tpu.dist.harness import run_dist
    from bcfl_tpu.telemetry import collate_run

    def dist_leg(name, lora_rank):
        c = FedConfig(
            name=name, runtime="dist", mode="server", sync="async",
            model=args.model, dataset="synthetic", num_labels=2,
            num_clients=args.dist_clients, num_rounds=args.dist_rounds,
            seq_len=16, batch_size=4, max_local_batches=2, eval_every=0,
            lora_rank=lora_rank,
            partition=PartitionConfig(kind="iid", iid_samples=8),
            ledger=LedgerConfig(enabled=True),
            dist=DistConfig(peers=args.peers, buffer_timeout_s=5.0,
                            # 3 peers compile round programs concurrently;
                            # on a contended host that alone can exceed 60s
                            idle_timeout_s=120.0,
                            peer_deadline_s=args.dist_deadline - 20.0,
                            checkpoint_every_versions=0))
        with tempfile.TemporaryDirectory() as td:
            r = run_dist(c, os.path.join(td, "run"),
                         deadline_s=args.dist_deadline, platform="cpu")
            if not r["ok"]:
                raise RuntimeError(f"{name} failed: {r['log_tails']}")
            col = collate_run(r["run_dir"])
            frames = [e["bytes"] for e in col["ordered"]
                      if e["ev"] == "send" and e.get("ok")
                      and e.get("type") == "update"]
            return {
                "process_count": r["process_count"],
                "update_frames": len(frames),
                "max_update_frame_bytes": max(frames) if frames else 0,
                "telemetry_ok": bool(col["ok"]),
                "chain_ok": all(r["reports"][p]["chain_ok"]
                                for p in range(args.peers)),
            }

    dist_adapter = dist_leg("lora_comm_dist_adapter", args.lora_rank)
    print(f"dist adapter: {dist_adapter}", flush=True)
    dist_full = dist_leg("lora_comm_dist_full", 0)
    print(f"dist full:    {dist_full}", flush=True)
    frame_frac = (dist_adapter["max_update_frame_bytes"]
                  / max(dist_full["max_update_frame_bytes"], 1))

    out = {
        "model": args.model, "clients": args.clients,
        "rounds": args.rounds, "lora_rank": args.lora_rank,
        "lora_ranks": args.lora_ranks, "loss_tol": args.loss_tol,
        "full": full, "adapter": adapter, "stacked_int8_topk": stacked,
        "adapter_reduction_x": round(reduction, 2),
        "stacked_reduction_x": round(stacked_x, 2),
        "adapter_loss_delta_vs_full": round(loss_delta, 5),
        "hetero": {
            "client_lora_ranks": list(het_cfg.client_lora_ranks),
            "effective_rank_per_round": het_eff,
            "final_train_loss": round(het_recs[-1].train_loss, 5),
            "server_round_cache_entries": het_retraces,
        },
        "dist": {
            "peers": args.peers, "clients": args.dist_clients,
            "rounds": args.dist_rounds,
            "adapter": dist_adapter, "full": dist_full,
            "update_frame_fraction_of_full": round(frame_frac, 5),
        },
        "pass_ge_reduction": reduction >= args.min_reduction,
        "pass_loss_matched": loss_delta <= args.loss_tol,
        "pass_hetero_effective_rank": (
            all(e is not None for e in het_eff) and het_retraces == 1),
        "pass_dist_frame_cap": frame_frac <= args.frame_cap,
        "pass_dist_invariants": (dist_adapter["telemetry_ok"]
                                 and dist_adapter["chain_ok"]),
    }
    ok = all(v for k, v in out.items() if k.startswith("pass_"))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({
        "adapter_reduction_x": out["adapter_reduction_x"],
        "stacked_reduction_x": out["stacked_reduction_x"],
        "update_frame_fraction": out["dist"]["update_frame_fraction_of_full"],
        "pass": ok,
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
