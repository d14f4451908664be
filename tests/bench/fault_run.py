"""Readings of what decides ``correct``: sound runs of a cell, runs of its
control (the configuration's lower-precision path), and runs with the timed
path broken underneath by each of ``FAULTS``. The harness's look for a chip
is skipped; everything else of a run is driven as ``bench/run.py`` drives it.

    JAX_PLATFORMS=cpu python tests/bench/fault_run.py <cell>
    python tests/bench/fault_run.py <cell> --full --seconds 10 \\
        --seeds 1,2,3 --control-seeds 1,2,3 --fault-seeds 1,2,3 \\
        --faults empty_half

By default the runs are at a tiny size on whatever JAX finds, on seed 5.
``--full`` runs them at the cell's own size, to read on the chip the numbers
that a limit is set from. Every run shares one index (the rows do not depend
on the seed). Each run prints one JSON line: its case, seed, ``correct`` and
the checks it was decided by; the last line holds them all, by case.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

N, POOL, RADIUS = 1500, 256, 0.03


def _drop_half(out):
    """Half of each batch's answers never come back."""
    return out[:len(out) // 2]


def _empty_half(out):
    """Half of each batch is left out: its answers come back with no ids."""
    for r in out[len(out) // 2:]:
        r.ids = []
    return out


def _alter(out):
    """One id of every answer is replaced where it is produced."""
    for r in out:
        ids = list(r.ids)
        ids[:1] = [int(ids[0]) + 1] if ids else [0]
        r.ids = ids
    return out


def _no_work(out):
    """The step returns without answering anything it drained."""
    return []


FAULTS = {"drop_half": _drop_half, "empty_half": _empty_half,
          "alter": _alter, "no_work": _no_work}


def _seeds(s):
    return [int(x) for x in s.split(",")] if s else []


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("cell")
    p.add_argument("--full", action="store_true")
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--seeds", default="5")
    p.add_argument("--control-seeds", default="5")
    p.add_argument("--fault-seeds", default="5")
    p.add_argument("--faults", default=",".join(FAULTS))
    args = p.parse_args(argv)

    from bench import harness
    from repro.serve import RangeServer
    build, built = harness.build, []
    harness.build = lambda *a: built[0] if built else (
        built.append(build(*a)) or built[0])
    size = {} if args.full else dict(n=N, pool=POOL, radius=RADIUS)

    res = {}

    def run(case, seed, **kw):
        out = harness.run_cell(args.cell, seed, args.seconds, False,
                               t_start=time.perf_counter(), **size, **kw)
        line = {"case": case, "seed": seed, "correct": out["correct"],
                "checks": out["checks"],
                "attempted": out["attempted"]}
        print(json.dumps(line), flush=True)
        res.setdefault(case, []).append(line)

    for seed in _seeds(args.seeds):
        run("none", seed)
    for seed in _seeds(args.control_seeds):
        run("control", seed, control=True)
    step = RangeServer.step
    for name in args.faults.split(","):
        broken = FAULTS[name]
        RangeServer.step = lambda self, b=broken: b(step(self))
        try:
            for seed in _seeds(args.fault_seeds):
                run(name, seed)
        finally:
            RangeServer.step = step
    print(json.dumps(res))


if __name__ == "__main__":
    main()
