#!/usr/bin/env python
"""Kernel microbench: pallas-vs-XLA wall per registered op, per shape.

Sweeps every op in the kernel registry (bcfl_tpu.ops.registry) that
declares ``bench_shapes`` — day one: the codec's ``int8_quantize`` /
``topk_select`` at the shapes the codec is actually paid at (BERT-base
leaf widths + the LoRA rank-2/4/8 adapter widths, COMPRESSION.md) and
``flash_attention`` at its transformer shapes. For each (op, shape, impl)
row the op is jitted, parity-checked against its XLA reference under the
SAME jit context, warmed, and timed to a completion fence
(bcfl_tpu.core.fence).

Off-TPU the Pallas rows run in interpret mode, so the numbers mean
"plumbing works", not "kernel is fast" — every row (and the file header)
is stamped ``plumbing_only: true`` on a non-TPU backend so a CPU artifact
can never be mistaken for silicon evidence. On a TPU the same invocation
needs zero new code.

Usage: python scripts/kernel_bench.py [--out results/kernel_bench.json]
       [--ops int8_quantize,topk_select] [--iters N]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# importing these registers the ops
import bcfl_tpu.ops.flash  # noqa: E402,F401
import bcfl_tpu.ops.grouped_matmul  # noqa: E402,F401
import bcfl_tpu.ops.pallas_codec  # noqa: E402,F401
from bcfl_tpu.core.fence import fence  # noqa: E402
from bcfl_tpu.core.hostenv import compile_cache  # noqa: E402
from bcfl_tpu.ops import registry  # noqa: E402


def _build(op_name: str, row: dict):
    """(args, kwargs) for one bench row — the op-specific shape contract."""
    key = jax.random.key(0)
    if op_name == "int8_quantize":
        C, N, chunk = row["C"], row["N"], row["chunk"]
        M = -(-N // chunk)
        g = jax.random.normal(key, (C, M, chunk), jnp.float32)
        u = jax.random.uniform(jax.random.fold_in(key, 1), g.shape)
        return (g, u), {"stochastic": True}
    if op_name == "topk_select":
        R, N = row["R"], row["N"]
        x = jax.random.normal(key, (R, N), jnp.float32)
        k = max(1, int(math.ceil(0.05 * N)))  # codec default topk_frac
        return (x,), {"k": k}
    if op_name == "flash_attention":
        B, H, S, D = row["B"], row["H"], row["S"], row["D"]
        q = jax.random.normal(key, (B, H, S, D), jnp.float32)
        kk = jax.random.normal(jax.random.fold_in(key, 1), q.shape)
        v = jax.random.normal(jax.random.fold_in(key, 2), q.shape)
        return (q, kk, v), {}
    if op_name == "moe_grouped_matmul":
        M, K, N, G = row["M"], row["K"], row["N"], row["G"]
        # outputs of about 0.25: under 2 in magnitude, where one bfloat16
        # rounding apart (float32 sums in another order) is inside the
        # bench's coarse allclose
        lhs = jax.random.normal(key, (M, K), jnp.bfloat16) * 0.2
        rhs = jax.random.normal(jax.random.fold_in(key, 1), (G, K, N),
                                jnp.bfloat16) * 0.02
        # ``live`` rows spread evenly over the groups; the rest belong to none
        sizes = jnp.full((G,), row["live"] // G, jnp.int32)
        return (lhs, rhs, sizes), {}
    raise SystemExit(f"no arg builder for op {op_name!r}; add one here")


def _parity_ok(op: registry.KernelOp, ref, got) -> bool:
    ref_l, got_l = jax.tree.leaves(ref), jax.tree.leaves(got)
    if op.parity == "bit-identical":
        return all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(ref_l, got_l))
    # pinned-tolerance ops (flash): the tight pin lives in the op's tests;
    # here a coarse allclose guards against timing a broken kernel
    return all(np.allclose(np.asarray(a), np.asarray(b), atol=2e-2)
               for a, b in zip(ref_l, got_l))


def _time_ms(fn, args, iters: int) -> float:
    out = fn(*args)
    fence(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    fence(out)
    return (time.perf_counter() - t0) / iters * 1000.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="results/kernel_bench.json")
    ap.add_argument("--ops", default="",
                    help="comma list; default = every op with bench_shapes")
    ap.add_argument("--iters", type=int, default=0,
                    help="timed iterations (default: 3 on TPU, 1 off-TPU "
                         "plumbing)")
    args = ap.parse_args()

    compile_cache()
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    plumbing = not on_tpu
    iters = args.iters or (3 if on_tpu else 1)
    names = ([n for n in args.ops.split(",") if n]
             or [n for n in registry.list_ops()
                 if registry.get_op(n).bench_shapes])
    rows = []
    for name in names:
        op = registry.get_op(name)  # loud rejection of a typo'd --ops
        for shape in op.bench_shapes:
            call_args, kw = _build(name, shape)
            ref = None
            for impl in ("xla", "pallas"):
                fn, resolved = registry.select(name, impl, *call_args, **kw)
                row = {
                    "op": name,
                    "label": shape["label"],
                    "shape": {k: v for k, v in shape.items() if k != "label"},
                    "impl": impl,
                    "resolved_impl": resolved,
                    "parity": op.parity,
                    "backend": backend,
                    "plumbing_only": plumbing,
                }
                if impl == "pallas" and not op.has_pallas:
                    row["status"] = "no_pallas_impl"
                    rows.append(row)
                    continue
                if impl == "pallas" and resolved != "pallas":
                    # the op's static predicate turns this shape away (e.g.
                    # a top-k row wider than the VMEM budget) — recorded,
                    # never hidden: production serves it from the reference
                    row["status"] = "declined"
                    rows.append(row)
                    continue
                jfn = jax.jit(lambda *a, _f=fn: _f(*a, **kw))
                out = jfn(*call_args)
                fence(out)
                if impl == "xla":
                    ref = out
                else:
                    row["parity_ok"] = _parity_ok(op, ref, out)
                    if not row["parity_ok"]:
                        row["status"] = "parity_violation"
                        rows.append(row)
                        continue  # never time a wrong kernel
                row["wall_ms"] = round(_time_ms(jfn, call_args, iters), 4)
                row["status"] = "ok"
                rows.append(row)
    doc = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "interpret_mode": registry.interpret_mode(),
        "plumbing_only": plumbing,
        "iters": iters,
        "generated_unix": int(time.time()),
        "rows": rows,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"{len(rows)} rows -> {args.out} "
          f"(backend={backend}, plumbing_only={plumbing})")
    bad = [r for r in rows if r["status"] == "parity_violation"]
    if bad:
        print(f"PARITY VIOLATIONS: {[(r['op'], r['label']) for r in bad]}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
