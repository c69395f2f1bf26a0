#!/usr/bin/env python3
"""On-chip benchmark of the sat-QFL round engine, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/workloads/<traffic>.json``). A run builds the cell's data,
weights and keys from ``--seed``, drives the first rounds as set-up (the
rounds the plain reference checks, and every shape the window uses), then
runs rounds back to back for ``--seconds`` and prints one JSON line:

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
     "checks"}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read by ``bench/metrics/<metric>.py`` from the profiler
trace of the window's first ``trace_seconds`` and from JAX's compile
events. ``correct`` compares the checked rounds with the reference
(``bench/harness/reference.py``) after the window has closed; every number
compared is printed beside its limit as the last lines on stderr.

Without a TPU, or with fewer chips than the cell asks for, the run prints
no result and exits non-zero. The compile cache lives in
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
N_CHECK = 3                       # rounds the reference follows


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def say(*a, err=False):
    print(*a, file=sys.stderr if err else sys.stdout, flush=True)


class CompileCounter:
    """Counts XLA compilations (fresh or loaded from the persistent
    cache), and persistent-cache hits, from JAX's monitoring events;
    ``phase`` says whose they are."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring
        self.phase = "setup"
        self.compiles = {"setup": 0, "window": 0, "after": 0}
        self.hits = dict(self.compiles)
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, duration, **kw):
        if event == self.COMPILE:
            self.compiles[self.phase] += 1
            if self.phase == "window":
                self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event == self.HIT:
            self.hits[self.phase] += 1


def run_window(engine, seconds: float, trace_seconds: float, trace_dir,
               counter) -> SimpleNamespace:
    """Rounds back to back for ``seconds``; with ``trace_dir`` the first
    ``trace_seconds`` of them under the profiler."""
    import jax
    w = SimpleNamespace(round_s=[], attempted=0, failed=0, traced_rounds=0,
                        first_round=engine.next_round, trace_path=None)
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # spans, not every Python call
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation("bench.window")
    counter.phase = "window"
    start = end = time.perf_counter()
    span.__enter__()
    span_open = True
    while end - start < seconds and engine.rounds_left() > 0:
        w.attempted += 1
        t = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.round"):
                ok = engine.step()
        except Exception:
            if w.failed == 0:
                traceback.print_exc()
            ok = False
        end = time.perf_counter()
        w.round_s.append(end - t)
        w.failed += not ok
        if trace_dir and span_open:
            w.traced_rounds += 1
            if end - start >= trace_seconds:
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                span_open = False
    if span_open:
        span.__exit__(None, None, None)
        if trace_dir:
            jax.profiler.stop_trace()
    counter.phase = "after"
    w.window_s = end - start
    w.completed = w.attempted - w.failed
    if trace_dir:
        found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        w.trace_path = found[0] if found else None
    return w


def main(argv=None, require_tpu: bool = True, use_cache: bool = True,
         root: str = ROOT) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        say(f"bench: no repro package under {SRC}; run from a checkout",
            err=True)
        return 2
    for p in (SRC, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import check, flops, spec, xplane

    cell = spec.load_cell(args.workload, Path(root))
    if use_cache:
        spec.use_checkout_cache()
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        say(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s); nothing was run",
            err=True)
        return 3
    counter = CompileCounter()
    traffic, config = cell.traffic, cell.config

    engine = spec.load_engine(traffic["engine"])(config, traffic, args.seed)
    engine.warm_up(N_CHECK)
    setup_s = time.perf_counter() - T_START
    for k, v in engine.parts.items():
        say(f"bench: set-up part {k} {v:.3f}")
    say(f"bench: set-up compiled {counter.compiles['setup']} programs, "
        f"{counter.hits['setup']} of them from the persistent cache")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        w = run_window(engine, args.seconds, traffic["trace_seconds"],
                       trace_dir, counter)
        tr = xplane.load(w.trace_path) if w.trace_path else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if engine.rounds_left() < 2 * w.attempted:
        say(f"bench: WARNING plan nearly spent: {engine.rounds_left()} of "
            f"{engine.plan_rounds} rounds left after a window of "
            f"{w.attempted}; lengthen plan_rounds", err=True)
    if counter.compiles["window"]:
        say(f"bench: {counter.compiles['window']} compilation(s) in the "
            f"window: {sorted(set(counter.names))}", err=True)
    used = devs[:cell.chips]
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)

    on_tpu = devs[0].platform == "tpu"
    ctx = SimpleNamespace(
        setup_s=setup_s, window_s=w.window_s, round_s=w.round_s,
        completed=w.completed, compiles=counter.compiles["window"], trace=tr,
        traced_rounds=w.traced_rounds, chips=cell.chips,
        peak=flops.peak(devs[0].device_kind) if on_tpu else None,
        traced_flops=engine.model_flops(w.first_round, w.traced_rounds))
    chosen = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for entry in chosen:
        v = spec.load_reader(entry["name"], Path(root))(ctx)
        if v is not None:
            metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak_mem)}
    out = {"correct": False, "attempted": w.attempted, "failed": w.failed,
           "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = xplane.busy_s(tr)
        device["window_s"] = xplane.window_s(tr)
        mods = sorted(xplane.module_seconds(tr).items(), key=lambda x: -x[1])
        ops = sorted(xplane.op_seconds(tr).items(), key=lambda x: -x[1])
        out["breakdown"] = {
            "device_ops": ([[f"program {k}", v] for k, v in mods[:5]]
                           + [[f"op {k}", v] for k, v in ops[:5]]),
            "idle_gaps": xplane.idle_gaps(tr)}

    # the check: after the window, with the program's state freed
    engine.free()
    gc.collect()
    t = time.perf_counter()
    covered = {}
    ref, ref_losses = engine.reference(N_CHECK, "highest", stats=covered)
    nums = check.numbers(engine.params0, engine.snapshots, engine.losses,
                         ref, ref_losses)
    say(f"bench: reference {time.perf_counter() - t:.3f} s over {N_CHECK} "
        f"rounds: {covered['merged']} updates merged, {covered['mains']} "
        f"mains trained")
    limits = traffic["limits"]
    out["correct"] = check.verdict(nums, limits)
    out["checks"] = {k: {"value": nums[k], "limit": limits[k]}
                     for k in limits}
    for k in limits:
        say(f"check {k} {nums[k]!r} limit {limits[k]!r}", err=True)
    say(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
