#!/usr/bin/env python3
"""Chip benchmark of the range-retrieval server: one run of one cell.

    python3 bench/run.py --workload bigann-int8.sat --seed 7 --seconds 10 --trace 0

Builds the cell's deployment from ``--seed`` (corpus, queries, index),
warms up the shapes its traffic uses, drives ``RangeServer`` for
``--seconds``, compares every answer with the brute-force reference, and
prints one JSON line last on stdout: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a profiler trace), ``device`` and
``checks`` (each compared number beside its limit; also the last lines on
stderr). Exits 1 without a result when JAX finds no TPU or fewer chips than
the cell asks for.

``--rehearse`` runs on whatever
JAX finds at a tiny size (``--n``, ``--pool``, ``--radius``) and exits 1
after, printing no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawTextHelpFormatter)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--n", type=int, default=0)
    p.add_argument("--pool", type=int, default=0)
    p.add_argument("--radius", type=float, default=0.0)
    args = p.parse_args(argv)

    from bench import harness
    manifest = harness.load_manifest()
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2

    import jax
    devs = jax.devices()
    if (args.n or args.pool or args.radius) and not args.rehearse:
        print("bench: --n, --pool and --radius are for --rehearse only",
              file=sys.stderr)
        return 2
    if devs[0].platform != "tpu" and not args.rehearse:
        print(f"bench: JAX found no TPU (platform {devs[0].platform!r}); "
              "nothing run", file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START, n=args.n,
                           pool=args.pool, radius=args.radius)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    if args.rehearse:
        print("bench: rehearsal, not a device result; no result printed",
              file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
