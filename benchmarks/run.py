#!/usr/bin/env python3
"""``python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One run of one cell (see benchmarks/harness.py). The last line of standard
output is the result object; the numbers compared beside their limits are
the last lines of standard error and the object's last key. ``--plumbing`` is
the harness's own switch for a CPU rehearsal at tiny sizes: it allows any
backend, marks the result ``plumbing_only`` and reports no device metric."""

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def process_start():
    try:
        import psutil

        return psutil.Process().create_time()
    except Exception:
        return T_IMPORT


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plumbing", action="store_true")
    args = ap.parse_args()
    t0 = process_start()

    if not os.path.isdir(os.path.join(ROOT, "bcfl_tpu")):
        print("benchmarks/run.py: no program here (bcfl_tpu/ is missing)", file=sys.stderr)
        return 4
    from benchmarks import harness, yardstick

    bench = harness.load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells and not args.plumbing:
        print(f"unknown workload {args.workload!r}; BENCHMARK.json has {sorted(cells)}",
              file=sys.stderr)
        return 2

    cache_dir = harness.place_compile_cache()
    import jax

    cell, _ = harness.load_cell(args.workload, args.plumbing)
    devs = jax.devices()
    if not args.plumbing:
        if devs[0].platform != "tpu":
            print(f"no accelerator: jax found {devs[0].platform}; the benchmark "
                  "measures on a TPU only (--plumbing rehearses on a CPU)", file=sys.stderr)
            return harness.EXIT_NO_DEVICE
        try:
            yardstick.peaks(devs[0].device_kind)
        except KeyError as e:
            print(str(e), file=sys.stderr)
            return harness.EXIT_NO_DEVICE
        if len(devs) != cell["chips"]:
            print(f"cell {args.workload} asks for {cell['chips']} chip(s), jax sees "
                  f"{len(devs)}", file=sys.stderr)
            return harness.EXIT_NO_DEVICE
    harness.log(f"[bench] {args.workload} seed {args.seed} on {len(devs)} x "
                f"{devs[0].device_kind}; compile cache {cache_dir}")
    result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                              plumbing=args.plumbing, t_process_start=t0, bench=bench)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
