#!/usr/bin/env python3
"""Readings that set a cell's limits: the program against the reference on
many seeds (the lower reading), and against it the control and the
planted faults on a few (the upper reading).

    python bench/control.py --workload <cell> --seeds 1,2,3 [--upper 3]

For every seed it builds the cell as ``bench/run.py`` does (one trace for
all seeds: it does not depend on the seed), drives the program through
the checked rounds, and compares it with the reference at full float32.
On the first ``--upper`` seeds it also puts in the program's place

  * ``control``: the reference at ``"high"``, three bfloat16 passes;
  * ``half_batch``: the reference training on the first half of every
    local batch, the mean taken over that half;

and compares each with the reference. A round that leaves the state
unchanged reads 1 on ``update1`` by construction and is not run. One
JSON line per seed and reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
N_CHECK = 3


def readings(engine, ref, ref_losses, prec: str, keep: float) -> dict:
    from harness import check
    got, losses = engine.reference(N_CHECK, prec, keep=keep)
    return check.numbers(engine.params0, got, losses, ref, ref_losses)


def main(argv=None, require_tpu: bool = True, use_cache: bool = True,
         root: str = ROOT, out=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--upper", type=int, default=3,
                    help="seeds that also read the control and faults")
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    from pathlib import Path

    from harness import check, inputs, spec

    cell = spec.load_cell(args.workload, Path(root))
    if use_cache:
        spec.use_checkout_cache()
    import jax
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        sys.exit(3)
    trace = inputs.build_constellation(cell.config, cell.traffic)
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), file=out or sys.stdout, flush=True)

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        engine = spec.load_engine(cell.traffic["engine"])(
            cell.config, cell.traffic, seed, trace=trace)
        engine.warm_up(N_CHECK, rounds=N_CHECK)
        engine.free()
        ref, ref_losses = engine.reference(N_CHECK, "highest")
        emit({"seed": seed, "reading": "program",
              **check.numbers(engine.params0, engine.snapshots,
                              engine.losses, ref, ref_losses),
              "seconds": time.perf_counter() - t})
        if i < args.upper:
            emit({"seed": seed, "reading": "control",
                  **readings(engine, ref, ref_losses, "high", 1.0)})
            emit({"seed": seed, "reading": "half_batch",
                  **readings(engine, ref, ref_losses, "highest", 0.5)})
    return rows


if __name__ == "__main__":
    main()
