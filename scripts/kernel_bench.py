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

The ``flash_attention`` rows run what a model hands the kernels: operands
in the bench shape's ``dtype``, its ``causal`` flag, a padded-key bias.
``--flash-blocks "256,256;1024,1024;1024,1024/512,1024/1024,512;default"``
adds one Pallas row a shape for each entry: a ``bq,bk`` pair for all three
kernels, three pairs ``forward/dKV/dQ``, or ``default``
(``pallas_flash.DEFAULT_BLOCKS``, what the models' dispatcher runs; the
one row when the flag is absent). ``--backward`` times ``jax.grad`` of a
sum through the op (forward and backward together) and, for the Pallas
rows, each of the three kernels alone (``kernel_ms``). A flash row's
``device_ms`` and ``kernel_ms`` are device time from a profiler trace (a
short call is bound by the host's dispatch on the wall clock). Each row records
the blocks it asked for, what the clamp made of them and the grids' step
counts. This is the sweep ``DEFAULT_BLOCKS`` was chosen by (PERF.md
section 7).

The ``ssm_scan`` rows take ``x`` as the model hands it over, ``[B, S, H P]``,
made ``[B, S, H, P]`` inside the timed program, so neither impl pays for a
relayout of a parameter the model never has. ``--scan-heads "16;32;default"``
adds one Pallas row for each number of heads a grid step takes
(``pallas_ssm.HEAD_BLOCK``); with ``--backward`` a row's ``device_ms`` is
``jax.grad`` of a sum (the backward pass, whose states pass stands in for the
forward: a sum's gradient needs no forward result) and
``forward_device_ms`` the forward pass alone, both by the device's clock; a
round of the cell runs both.

Usage: python scripts/kernel_bench.py [--out results/kernel_bench.json]
       [--ops int8_quantize,topk_select] [--iters N] [--backward]
       [--flash-blocks "bq,bk[;bq,bk...]"] [--scan-heads "h[;h...]"]
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# importing these registers the ops
import bcfl_tpu.ops.flash  # noqa: E402,F401
import bcfl_tpu.ops.grouped_matmul  # noqa: E402,F401
import bcfl_tpu.ops.pallas_codec  # noqa: E402,F401
import bcfl_tpu.ops.ssm_scan  # noqa: E402,F401
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
        q, kk, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D),
                                      jnp.dtype(row.get("dtype", "float32")))
                    for i in range(3))
        # padded keys: row 0 whole, each further row an eighth shorter
        lens = S - (jnp.arange(B) % 8) * (S // 8)
        bias = jnp.where(jnp.arange(S)[None, :] < lens[:, None], 0.0, -1e30)
        return (q, kk, v, bias), {"causal": row.get("causal", False)}
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
    if op_name == "ssm_scan":
        B, S, H, P, N = row["B"], row["S"], row["H"], row["P"], row["N"]
        dtype = jnp.dtype(row.get("dtype", "float32"))
        ks = [jax.random.fold_in(key, i) for i in range(6)]
        # x as the model hands it over: [B, S, H P], made [B, S, H, P] inside
        # the timed program (``_scan_fn``), so neither impl pays a relayout
        # of a parameter the model never has
        x, Bm, Cm = (0.5 * jax.random.normal(k, shape, dtype) for k, shape in
                     zip(ks, ((B, S, H * P), (B, S, N), (B, S, N))))
        # Mamba-2's draw: dt log-uniform in [0.001, 0.1], A uniform in [-16, -1]
        dt = jnp.exp(jax.random.uniform(ks[3], (B, S, H), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        A = -jax.random.uniform(ks[4], (H,), jnp.float32, 1.0, 16.0)
        return (x, dt, A, Bm, Cm, jnp.ones((H,), jnp.float32)), {"chunk": row["chunk"]}
    raise SystemExit(f"no arg builder for op {op_name!r}; add one here")


def _parity_ok(op: registry.KernelOp, ref, got) -> bool:
    ref_l, got_l = jax.tree.leaves(ref), jax.tree.leaves(got)
    if op.parity == "bit-identical":
        return all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(ref_l, got_l))
    # pinned-tolerance ops (flash): the tight pin lives in the op's tests;
    # here a coarse bound guards against timing a broken kernel, read
    # against the reference's own magnitude (bfloat16 values of 4 and over
    # are one rounding, 3e-2, apart)
    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return np.abs(a - b).max() <= 2e-2 * max(1.0, np.abs(a).max())

    return all(close(a, b) for a, b in zip(ref_l, got_l))


def _time_ms(fn, args, iters: int) -> float:
    out = fn(*args)
    fence(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    fence(out)
    return round((time.perf_counter() - t0) / iters * 1000.0, 4)


def _device_ms(fn, args, iters: int):
    """Device time of one call, ms: the busy time of the first TPU's "XLA
    Ops" line over a traced loop of ``iters`` warm calls (the union of its
    events: a loop's body nests under the loop). A call of a fraction of a
    millisecond is bound by the host's dispatch on the wall clock (a
    quarter of a millisecond an output array on the chip tool's machine,
    PR 29), so block sizes at short rows can only be told apart here. None
    off a TPU: a CPU run has no device time."""
    if jax.default_backend() != "tpu":
        return None
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(iters):
                out = fn(*args)
            fence(out)
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        planes = jax.profiler.ProfileData.from_file(path).planes
        (ops,) = [line for plane in planes if plane.name == "/device:TPU:0"
                  for line in plane.lines if line.name == "XLA Ops"]
        spans = sorted((e.start_ns, e.end_ns) for e in ops.events)
    busy, end = 0.0, 0.0
    for s, e in spans:
        busy += max(0.0, e - max(s, end))
        end = max(end, e)
    return round(busy / iters / 1e6, 4)


def _flash_variants(spec: str):
    """``--flash-blocks`` -> ``[(label, {kernel: (bq, bk)} or None)]``;
    None is ``pallas_flash.DEFAULT_BLOCKS`` as the module has it."""
    out = []
    for entry in (e.strip() for e in (spec or "default").split(";")):
        if entry == "default":
            out.append((entry, None))
            continue
        pairs = [tuple(int(x) for x in p.split(",")) for p in entry.split("/")]
        if len(pairs) not in (1, 3) or any(len(p) != 2 for p in pairs):
            raise SystemExit(f"--flash-blocks: {entry!r} is neither 'bq,bk', "
                             "'bq,bk/bq,bk/bq,bk' (forward/dKV/dQ) nor 'default'")
        out.append((entry, dict(zip(("fwd", "dkv", "dq"), pairs * 3))))
    return out


def _scan_variants(spec: str):
    """``--scan-heads`` -> ``[(label, heads or None)]``; None is
    ``pallas_ssm.HEAD_BLOCK`` as the module has it."""
    return [(e, None if e == "default" else int(e))
            for e in (e.strip() for e in (spec or "default").split(";"))]


@contextlib.contextmanager
def _scan_heads(heads):
    """Run with ``pallas_ssm.HEAD_BLOCK`` set to ``heads`` (None: as the
    module has it): the kernels read it when they are traced."""
    from bcfl_tpu.ops import pallas_ssm as ps

    kept = ps.HEAD_BLOCK
    ps.HEAD_BLOCK = heads or kept
    try:
        yield
    finally:
        ps.HEAD_BLOCK = kept


def _scan_fn(fn, shape: dict, chunk: int, backward: bool):
    """The timed scan program: ``x`` [B, S, H P] reshaped to heads inside;
    with ``backward``, ``jax.grad`` of a sum (forward and backward)."""
    heads = (shape["B"], shape["S"], shape["H"], shape["P"])

    def scan(x, *rest):  # positional: custom_vjp functions take no keywords
        return fn(x.reshape(heads), *rest, chunk)

    if backward:
        return jax.jit(jax.grad(lambda *a: scan(*a).astype(jnp.float32).sum(),
                                argnums=(0, 1, 3, 4)))
    return jax.jit(scan)


@contextlib.contextmanager
def _flash_blocks(blocks):
    """Run with ``pallas_flash.DEFAULT_BLOCKS`` set to ``blocks`` (None:
    as the module has it): a kernel reads it when it is traced."""
    from bcfl_tpu.ops import pallas_flash as pf

    kept = pf.DEFAULT_BLOCKS
    pf.DEFAULT_BLOCKS = blocks or kept
    try:
        yield
    finally:
        pf.DEFAULT_BLOCKS = kept


def _flash_row(call_args, causal: bool, backward: bool, iters: int):
    """What the three kernels make of ``pallas_flash.DEFAULT_BLOCKS`` at
    this shape and, with ``backward``, each kernel's own time."""
    from bcfl_tpu.ops import pallas_flash as pf

    q, k, v, bias = call_args
    B, H, S, D = q.shape
    Sk = k.shape[2]
    legal = {name: pf._blocks(name, None, None, S, Sk, D, q.dtype)[:2]
             for name in pf.DEFAULT_BLOCKS}
    info = {
        "blocks_requested": {n: list(p) for n, p in pf.DEFAULT_BLOCKS.items()},
        "blocks": {n: list(p) for n, p in legal.items()},
        "grid_steps": {n: B * H * -(-S // bq) * -(-Sk // bk)
                       for n, (bq, bk) in legal.items()},
    }
    if backward:
        fwd = jax.jit(lambda q, k, v, b: pf._flash_fwd_pallas(
            q, k, v, b, causal, None, None))
        out, lse = fwd(q, k, v, bias)
        bwd_args = (q, k, v, bias, out, jnp.ones_like(out), lse)
        dkv = jax.jit(lambda *a: pf._flash_bwd_dkv_pallas(*a, causal, None, None))
        dq = jax.jit(lambda *a: pf._flash_bwd_dq_pallas(*a, causal, None, None))
        # each kernel alone in a program of its own; on a TPU by the
        # device's clock
        info["kernel_ms"] = {
            name: _device_ms(f, a, iters) or _time_ms(f, a, iters)
            for name, f, a in (("fwd", fwd, (q, k, v, bias)),
                               ("dkv", dkv, bwd_args), ("dq", dq, bwd_args))}
    return info


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="results/kernel_bench.json")
    ap.add_argument("--ops", default="",
                    help="comma list; default = every op with bench_shapes")
    ap.add_argument("--iters", type=int, default=0,
                    help="timed iterations (default: 3 on TPU, 1 off-TPU "
                         "plumbing)")
    ap.add_argument("--backward", action="store_true",
                    help="flash_attention, ssm_scan: time jax.grad of a sum "
                         "through the op (flash: and each Pallas kernel alone)")
    ap.add_argument("--flash-blocks", default="",
                    help="flash_attention: 'bq,bk[;bq,bk...]', an entry "
                         "'f,f/k,k/q,q' names forward/dKV/dQ apart, "
                         "'default' is pallas_flash.DEFAULT_BLOCKS")
    ap.add_argument("--scan-heads", default="",
                    help="ssm_scan: 'h[;h...]' heads a grid step takes, "
                         "'default' is pallas_ssm.HEAD_BLOCK")
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
        flash = name == "flash_attention"
        scan = name == "ssm_scan"
        for shape in op.bench_shapes:
            call_args, kw = _build(name, shape)
            ref = None
            # one Pallas row, or one for each --flash-blocks or
            # --scan-heads entry
            pallas_rows = (_flash_variants(args.flash_blocks) if flash
                           else _scan_variants(args.scan_heads) if scan
                           else [(None, None)])
            for impl, (label, blocks) in [("xla", (None, None))] + [
                    ("pallas", v) for v in pallas_rows]:
                sel_args = call_args
                if scan:  # the op's own view: x [B, S, H, P]
                    sel_args = (jax.ShapeDtypeStruct(
                        (shape["B"], shape["S"], shape["H"], shape["P"]),
                        call_args[0].dtype),) + call_args[1:]
                fn, resolved = registry.select(name, impl, *sel_args, **kw)
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
                if label:
                    row["scan_heads" if scan else "flash_blocks"] = label
                rows.append(row)
                if impl == "pallas" and not op.has_pallas:
                    row["status"] = "no_pallas_impl"
                    continue
                if impl == "pallas" and resolved != "pallas":
                    # the op's static predicate turns this shape away (e.g.
                    # a top-k row wider than the VMEM budget) — recorded,
                    # never hidden: production serves it from the reference
                    row["status"] = "declined"
                    continue
                if flash and args.backward:
                    row["timed"] = "grad of a sum (forward + backward)"
                    jfn = jax.jit(jax.grad(
                        lambda q, k, v, b, _f=fn: _f(q, k, v, b, **kw).astype(
                            jnp.float32).sum(), argnums=(0, 1, 2)))
                elif scan:
                    if args.backward:
                        row["timed"] = "grad of a sum (forward + backward)"
                    jfn = _scan_fn(fn, shape, kw["chunk"], args.backward)
                else:
                    jfn = jax.jit(lambda *a, _f=fn: _f(*a, **kw))
                with _flash_blocks(None if scan else blocks), \
                        _scan_heads(blocks if scan else None):
                    out = jfn(*call_args)
                    fence(out)
                    if impl == "xla":
                        ref = out
                    else:
                        row["parity_ok"] = _parity_ok(op, ref, out)
                        if not row["parity_ok"]:
                            row["status"] = "parity_violation"
                            continue  # never time a wrong kernel
                    row["wall_ms"] = _time_ms(jfn, call_args, iters)
                    if flash or scan:
                        row["device_ms"] = _device_ms(jfn, call_args, iters)
                    if scan and args.backward:
                        fwd = _scan_fn(fn, shape, kw["chunk"], False)
                        row["forward_device_ms"] = _device_ms(fwd, call_args, iters)
                    if flash and impl == "pallas":
                        row.update(_flash_row(call_args, kw["causal"],
                                              args.backward, iters))
                row["status"] = "ok"
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
