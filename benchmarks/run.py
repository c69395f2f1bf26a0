"""Benchmark harness entry — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run             # quick mode (CI)
    PYTHONPATH=src python -m benchmarks.run --full      # paper-scale

Prints ``name,us_per_call,derived`` CSV (wall time of the benchmark body;
derived = the benchmark's headline result). Full JSON payloads land in
results/.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import csv_row, save_json


def _run_one(name, fn):
    t0 = time.perf_counter()
    payload, derived = fn()
    us = (time.perf_counter() - t0) * 1e6
    save_json(f"bench_{name}.json", payload)
    print(csv_row(name, us, derived))
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_comm, bench_constellation, bench_faults,
                            bench_frameworks, bench_kernels, bench_round,
                            bench_security, bench_vqc, roofline)

    if args.full:
        benches = {
            "frameworks_statlog": lambda: (bench_frameworks.run(
                "statlog", n_sats=50, n_rounds=20, local_steps=10), ""),
            "frameworks_eurosat": lambda: (bench_frameworks.run(
                "eurosat", n_sats=50, n_rounds=20, local_steps=10), ""),
            "teleport": lambda: (bench_security.teleport(
                n_sats=20, n_rounds=10, local_steps=8), ""),
            "qkd": lambda: (bench_security.qkd(
                n_sats=20, n_rounds=10, local_steps=8), ""),
            "security": bench_security.full,
            "comm": lambda: (bench_comm.comm_times(
                n_sats=50, n_rounds=10, local_steps=8), ""),
            "constellation": lambda: (bench_constellation.scenario(), ""),
            "kernels": bench_kernels.quick,
            "vqc": bench_vqc.quick,
            "round": bench_round.quick,
            "faults": bench_faults.full,
            "roofline": roofline.full,
        }
    else:
        benches = {
            "frameworks": bench_frameworks.quick,
            "security": bench_security.quick,
            "comm": bench_comm.quick,
            "constellation": bench_constellation.quick,
            "kernels": bench_kernels.quick,
            "vqc": bench_vqc.quick,
            "round": bench_round.quick,
            "faults": bench_faults.quick,
            "roofline": roofline.quick,
        }

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches.items():
        if args.only and args.only not in name:
            continue
        try:
            _run_one(name, fn)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(csv_row(name, float("nan"), f"ERROR {e!r}"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
