"""Measure communication-compression effect: bytes-on-wire + overhead.

Runs the same synthetic federated config across the update-exchange codecs
(COMPRESSION.md) and reports, per codec: bytes-on-wire per round (raw vs
compressed, from the engine's own accounting), round wall time (the codec's
in-graph compute overhead), and final train loss (error-feedback quality
check). Artifact-gated like ``scripts/ledger_overhead.py``: writes
``results/comm_overhead.json`` with the acceptance flags — int8+topk must
record a >= 4x reduction in bytes-on-wire per round AND reach the
uncompressed run's final loss within tolerance.

Convergence framing: error-feedback sparsification trades ROUNDS for BYTES —
the kept-coordinate budget delays the transient (the dropped mass transmits
in later rounds via the residual), so the sparsified codecs get
``--compressed-rounds`` (> ``--rounds``) to reach the uncompressed target;
the artifact records cumulative bytes to that loss, which is where the real
win shows (measured: int8+topk reaches the 6-round uncompressed loss in 10
rounds at ~9x fewer TOTAL bytes on the tiny model).

Usage: python scripts/comm_overhead.py [--model tiny-bert] [--clients 8]
           [--rounds 6] [--compressed-rounds 10] [--platform cpu]
           [--topk-frac 0.05]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny-bert")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--compressed-rounds", type=int, default=10,
                    help="round budget for the SPARSIFIED codecs "
                         "(topk/int8+topk): error feedback transmits the "
                         "dropped mass over later rounds, so reaching the "
                         "uncompressed loss takes more rounds — at a "
                         "fraction of the total bytes (module docstring)")
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--topk-frac", type=float, default=0.05)
    ap.add_argument("--loss-tol", type=float, default=0.05,
                    help="int8+topk's final loss must be <= the "
                         "uncompressed final loss + this")
    ap.add_argument("--platform", default=None)
    ap.add_argument("--out", default="results/comm_overhead.json")
    args = ap.parse_args(argv)

    from bcfl_tpu.core.hostenv import compile_cache

    compile_cache()

    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)

    import numpy as np

    from bcfl_tpu.compression import KINDS as CODECS, CompressionConfig
    from bcfl_tpu.config import FedConfig, PartitionConfig
    from bcfl_tpu.fed.engine import FedEngine

    def cfg(kind, rounds):
        return FedConfig(
            name=f"comm_{kind}", dataset="synthetic", num_labels=2,
            seq_len=args.seq_len, batch_size=16, vocab_size=2048,
            model=args.model, num_clients=args.clients,
            num_rounds=rounds, max_local_batches=2,
            learning_rate=3e-4, eval_every=0,
            partition=PartitionConfig(kind="iid", iid_samples=32),
            compression=CompressionConfig(kind=kind,
                                          topk_frac=args.topk_frac))

    rows = {}
    for kind in CODECS:
        # sparsified codecs get the extended round budget (docstring)
        rounds = (args.compressed_rounds if "topk" in kind else args.rounds)
        res = FedEngine(cfg(kind, rounds)).run()
        recs = res.metrics.rounds
        rows[kind] = {
            "rounds": rounds,
            "bytes_raw_per_round": recs[0].bytes_raw,
            "bytes_on_wire_per_round": recs[0].bytes_on_wire,
            "compression_ratio": round(recs[0].compression_ratio, 2),
            "total_bytes_on_wire": recs[0].bytes_on_wire * rounds,
            # skip round 0: it carries every program compile
            "round_wall_s_mean": round(
                float(np.mean([r.wall_s for r in recs[1:]])), 4),
            "final_train_loss": round(recs[-1].train_loss, 5),
            "info_passing_sync_s": round(recs[-1].info_passing_sync_s, 4),
        }
        print(f"{kind}: {rows[kind]}", flush=True)

    base = rows["none"]
    best = rows["int8+topk"]
    # acceptance pair: >= 4x fewer bytes PER ROUND, and the compressed run
    # reaches (or beats) the uncompressed final loss within tolerance over
    # its round budget — at how many x fewer TOTAL bytes is also recorded
    loss_delta = best["final_train_loss"] - base["final_train_loss"]
    # codec compute overhead: int8 vs none — the two runs with EQUAL round
    # budgets (comparing across different budgets once recorded a
    # physically impossible negative overhead). Host wall on a contended
    # CPU mesh is noisy: reported for orientation, never gated.
    overhead_pct = 100.0 * (rows["int8"]["round_wall_s_mean"]
                            / max(base["round_wall_s_mean"], 1e-9) - 1.0)
    out = {
        "model": args.model, "clients": args.clients,
        "rounds": args.rounds, "compressed_rounds": args.compressed_rounds,
        "seq_len": args.seq_len, "topk_frac": args.topk_frac,
        "rows": rows,
        "int8_topk_reduction_x": best["compression_ratio"],
        "int8_topk_total_bytes_reduction_x": round(
            base["total_bytes_on_wire"]
            / max(best["total_bytes_on_wire"], 1), 2),
        "int8_topk_loss_delta_vs_none": round(loss_delta, 5),
        "codec_wall_overhead_pct_int8_vs_none_noisy": round(overhead_pct, 2),
        "pass_ge_4x_reduction": best["compression_ratio"] >= 4.0,
        "pass_loss_within_tol": loss_delta <= args.loss_tol,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps({
        "comm_reduction_x": out["int8_topk_reduction_x"],
        "loss_delta": out["int8_topk_loss_delta_vs_none"],
        "pass": out["pass_ge_4x_reduction"] and out["pass_loss_within_tol"],
    }), flush=True)
    return 0 if (out["pass_ge_4x_reduction"]
                 and out["pass_loss_within_tol"]) else 1


if __name__ == "__main__":
    sys.exit(main())
