#!/usr/bin/env python3
"""Chip smoke run: the sat-QFL round engine once on a TPU, at full width.

    python chip_smoke.py [--seed N]        # one chip, every phase below
    python chip_smoke.py --four-chips      # four chips, the sharded round only

The model is the paper's VQC as configured (``vqc-satqfl``: 8 qubits,
3 layers, 7 classes) and the scenario is ``build_trace()`` at its
defaults (50 satellites in 10 planes, 10 ground stations, 6 h at 30 s),
with synthetic Statlog made from ``--seed``, Dirichlet-partitioned over
the satellites after the server split. Nothing is cut to size.

Phases, all in this one process on ``jax.devices()[0]``:

  host    ``SatQFLTrainer`` (constellation-batched, edge-batched, QKD) in
          sim, seq, async and qfl; async with secure aggregation; sim with
          parameter-shift gradients. Three rounds with participants each.
  oracle  sim (unsecured) against its per-client oracle on the chip (the
          repo's 1e-6 parity bound); round 0 of the QKD sim run against
          the same round on the host CPU.
  dist    ``make_fl_round`` (sim, otp) fed from ``plan.dist_inputs(r)``.
  kernels every ``repro.kernels`` entry with ``interpret=False`` against
          its ``ref.py`` oracle (the otp_xor outputs bit for bit).

``--four-chips`` runs only the dist round on 48 satellites with the
satellite axis sharded over a ("data",) mesh of four chips, against the
same round on one chip.

Times printed are smoke timings (host clock around ``block_until_ready``),
not benchmark metrics. The script exits non-zero, without the result
line, when JAX finds no TPU or any check fails; the last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

N_ROUNDS = 20           # the plan's rounds over the 6 h trace (one / 18 min)
RUN_ROUNDS = 3          # rounds with participants run per phase
PARITY_ATOL = 1e-6      # batched vs per-client oracle (tests/test_batched_round)
SHARD_ATOL = 1e-5       # four-chip vs one-chip aggregated params
# kernel vs ref.py tolerances, as tests/test_kernels.py holds them
GATE_ATOL, SWA_ATOL, SSD_ATOL = 2e-6, 2e-5, 3e-5

FAILED: list[str] = []


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        FAILED.append(what)


def phase(name: str, fn, *args):
    print(f"== {name}", flush=True)
    t = time.perf_counter()
    try:
        return fn(*args)
    except Exception:                       # reported, and the run fails
        traceback.print_exc()
        FAILED.append(f"{name}: raised")
        return None
    finally:
        print(f"  ({name}: {time.perf_counter() - t:.1f} s wall)", flush=True)


def leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def on_platform(tree, platform: str) -> bool:
    import jax
    return all(isinstance(x, jax.Array)
               and all(d.platform == platform for d in x.devices())
               for x in leaves(tree))


def max_abs_diff(a, b) -> float:
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.complex128)
                                   - np.asarray(y, np.complex128))))
               for x, y in zip(leaves(a), leaves(b)))


def host_copy(tree):
    import jax
    import numpy as np
    return jax.tree_util.tree_map(np.asarray, tree)


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------

def workload(seed: int, n_sats: int = 50, n_planes: int = 10):
    from repro.constellation import build_trace
    from repro.data import dirichlet_partition, make_statlog, server_split
    from repro.models import get_config, get_model

    cfg = get_config("vqc-satqfl")
    api = get_model(cfg)
    trace = build_trace(n_sats=n_sats, n_planes=n_planes)
    X, y = make_statlog(n_features=cfg.n_features, seed=seed)
    Xc, yc, server = server_split(X, y, seed=seed)
    sats = dirichlet_partition(Xc, yc, n_sats, seed=seed)
    print(f"  vqc-satqfl {cfg.vqc_qubits} qubits x {cfg.vqc_layers} layers, "
          f"{cfg.n_classes} classes; trace {trace.n_sats} sats x "
          f"{trace.n_steps} steps; statlog {tuple(X.shape)} seed {seed}")
    return dict(cfg=cfg, api=api, trace=trace, sats=sats, server=server,
                seed=seed)


def timing_line(label: str, setup_s: float, round_s: list) -> None:
    steady = statistics.median(round_s) * 1e3 if round_s else float("nan")
    print(f"  smoke timing {label}: setup {setup_s:.2f} s (plan compile + "
          f"jit + first round), steady {steady:.1f} ms/round over "
          f"{len(round_s)} rounds", flush=True)


# ---------------------------------------------------------------------------
# host engine
# ---------------------------------------------------------------------------

HOST_PHASES = [
    ("sim", {}), ("seq", {}), ("async", {}), ("qfl", {}),
    ("async", {"agg_security": "secagg"}),
    ("sim", {"grad_method": "param_shift"}),
]


def run_host(w, mode: str, extra: dict, batched: bool = True,
             security: str = "qkd"):
    """RUN_ROUNDS rounds with participants, every round before them run
    too (async carries its staleness ring from round to round)."""
    import jax
    from repro.core import SatQFLConfig, SatQFLTrainer

    fl = SatQFLConfig(mode=mode, n_rounds=N_ROUNDS, security=security,
                      seed=w["seed"], **extra)
    t0 = time.perf_counter()
    tr = SatQFLTrainer(w["cfg"], w["api"], fl, w["trace"], w["sats"],
                       w["server"], batched=batched, edge_batched=True)
    p0 = host_copy(tr.global_params)
    active = [r for r in range(N_ROUNDS) if tr.plan.participants(r) > 0]
    hist, round_s = [], []
    for r in range(active[RUN_ROUNDS - 1] + 1):
        t = time.perf_counter()
        hist.append(tr.run_round(r))
        jax.block_until_ready(tr.global_params)
        round_s.append(time.perf_counter() - t)
    setup_s = time.perf_counter() - t0 - sum(round_s[1:])
    return tr, p0, hist, setup_s, round_s[1:]


def host_phase(w, mode: str, extra: dict):
    label = mode + "".join(f" {k}={v}" for k, v in extra.items())
    tr, p0, hist, setup_s, round_s = run_host(w, mode, extra)
    for m in hist:
        print(f"  round {m.round}: val_loss={m.server_val_loss:.6f} "
              f"val_acc={m.server_val_acc:.4f} "
              f"participants={m.participants}", flush=True)
    timing_line(label, setup_s, round_s)
    check(all(math.isfinite(m.server_val_loss)
              and math.isfinite(m.dev_val_loss) for m in hist),
          f"{label}: every loss finite")
    check(any(m.participants > 0 for m in hist),
          f"{label}: a round with participants > 0")
    check(max_abs_diff(tr.global_params, p0) > 0,
          f"{label}: global params moved")
    check(on_platform(tr.global_params, "tpu"),
          f"{label}: global params on the TPU")
    return tr, hist


def oracle_phase(w, chip_hist):
    """sim batched vs its per-client oracle on the chip, and round 0 of
    the QKD sim run vs the same round on the host CPU.

    The parity pair runs unsecured: the per-edge Algorithm 2 oracle pays
    host round trips per edge (~19 s a round at 50 satellites on a v5e),
    and tests/test_batched_security.py holds the secure plane bit for bit.
    """
    import jax

    hists = {}
    for batched in (True, False):
        _, _, hists[batched], setup_s, round_s = run_host(
            w, "sim", {}, batched=batched, security="none")
        timing_line(f"sim {'batched' if batched else 'per-client oracle'}"
                    f" unsecured", setup_s, round_s)
    worst = 0.0
    for mb, mo in zip(hists[True], hists[False]):
        for f in ("server_val_loss", "server_val_acc", "server_test_acc"):
            worst = max(worst, abs(getattr(mb, f) - getattr(mo, f)))
        check(mb.comm_s == mo.comm_s and mb.participants == mo.participants,
              f"round {mb.round}: oracle accounting identical")
    print(f"  batched vs per-client oracle: max metric diff {worst:.3e}")
    check(worst <= PARITY_ATOL,
          f"sim batched vs oracle within {PARITY_ATOL:g}")

    cpu = jax.devices("cpu")[0]
    from repro.core import SatQFLConfig, SatQFLTrainer
    with jax.default_device(cpu):
        fl = SatQFLConfig(mode="sim", n_rounds=N_ROUNDS, security="qkd",
                          seed=w["seed"])
        tr = SatQFLTrainer(w["cfg"], w["api"], fl, w["trace"],
                           jax.device_put(w["sats"], cpu),
                           jax.device_put(w["server"], cpu))
        m0 = tr.run_round(0)
    print(f"  round 0 server val_loss: chip {chip_hist[0].server_val_loss:.9f}"
          f" cpu {m0.server_val_loss:.9f} diff "
          f"{abs(chip_hist[0].server_val_loss - m0.server_val_loss):.3e}")
    check(on_platform(tr.global_params, "cpu"), "cpu run stayed on the cpu")


# ---------------------------------------------------------------------------
# in-graph (dist) engine
# ---------------------------------------------------------------------------

def run_dist(w, place):
    """make_fl_round (sim, otp) for RUN_ROUNDS rounds with participants;
    ``place(tree)`` puts a satellite-stacked tree where it runs."""
    import jax
    import numpy as np
    from repro.core import SatQFLConfig, compile_round_plan
    from repro.core.dist import (fl_init_state, make_fl_round,
                                 sample_round_batches, stack_client_data)
    from repro.nn.optim import sgd

    cfg, api, n_sats = w["cfg"], w["api"], w["trace"].n_sats
    fl = SatQFLConfig(mode="sim", n_rounds=N_ROUNDS, security="qkd",
                      seed=w["seed"])
    t0 = time.perf_counter()
    plan = compile_round_plan(
        w["trace"], fl, sample_counts=[len(s["labels"]) for s in w["sats"]],
        with_seeds=True)
    opt = sgd(fl.lr)
    state = place(fl_init_state(cfg, api, opt, n_sats,
                                jax.random.PRNGKey(w["seed"])))
    p0 = host_copy(state.params)
    data = place(stack_client_data(w["sats"]))
    round_fn = jax.jit(make_fl_round(cfg, api, fl, opt, n_sats,
                                     security="otp"))
    rng = np.random.default_rng(w["seed"])
    rounds = [r for r in range(N_ROUNDS)
              if plan.participants(r) > 0][:RUN_ROUNDS]
    losses, round_s = [], []
    for r in rounds:
        t = time.perf_counter()
        batches = place(sample_round_batches(data, rng, fl.local_steps,
                                             fl.batch_size))
        mask, seeds, weights = plan.dist_inputs(r)
        state, metrics = round_fn(state, batches, mask, seeds, weights)
        jax.block_until_ready(state)
        round_s.append(time.perf_counter() - t)
        losses.append(float(metrics["loss"]))
        print(f"  round {r}: local_loss={losses[-1]:.6f} "
              f"participants={plan.participants(r)}", flush=True)
    setup_s = time.perf_counter() - t0 - sum(round_s[1:])
    return state, p0, losses, setup_s, round_s[1:]


def dist_checks(label, state, p0, losses):
    check(all(math.isfinite(x) for x in losses), f"{label}: every loss finite")
    check(max_abs_diff(state.params, p0) > 0, f"{label}: params moved")


def dist_phase(w):
    import jax
    dev = jax.devices()[0]
    state, p0, losses, setup_s, round_s = run_dist(
        w, lambda t: jax.device_put(t, dev))
    timing_line("dist sim otp", setup_s, round_s)
    dist_checks("dist", state, p0, losses)
    check(on_platform(state, "tpu"), "dist: every state array on the TPU")


def four_chip_phase(seed: int):
    """The dist round with the satellite axis sharded over four chips vs
    the same round on one chip."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.dist import sat_axis_shardings

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs), ("data",))
    # 48 satellites in 8 planes: the satellite axis splits evenly over 4
    w = workload(seed, n_sats=48, n_planes=8)
    sharded, p0, losses, setup_s, round_s = run_dist(
        w, lambda t: jax.device_put(t, sat_axis_shardings(t, mesh)))
    timing_line("dist sim otp, 4 chips", setup_s, round_s)
    dist_checks("4-chip dist", sharded, p0, losses)
    single, _, _, setup_s, round_s = run_dist(
        w, lambda t: jax.device_put(t, devs[0]))
    timing_line("dist sim otp, 1 chip", setup_s, round_s)
    spans = [len(x.sharding.device_set) for x in leaves(sharded.params)]
    check(all(n == 4 for n in spans),
          f"4-chip params span 4 devices (per leaf: {sorted(set(spans))})")
    diff = max_abs_diff(sharded.params, single.params)
    print(f"  4-chip vs 1-chip aggregated params: max diff {diff:.3e}")
    check(diff <= SHARD_ATOL, f"4-chip params within {SHARD_ATOL:g} "
          f"of 1-chip")
    check(on_platform(sharded, "tpu"), "4-chip state on TPUs")


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def kernel_cases(seed: int):
    """(name, kernel fn, ref.py fn, args, atol) at the compile-test
    shapes; atol 0 means bit for bit. Both sides run jitted."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import (apply_gate, apply_gate_layer, otp_xor_mac,
                               otp_xor_mac_edges, ssd_scan, swa_attention)
    from repro.kernels.otp_xor.ref import otp_xor_mac_ref
    from repro.kernels.ssd_scan.ref import ssd_scan_ref
    from repro.kernels.statevec_gate.ops import _pack_gate, _pack_gates
    from repro.kernels.statevec_gate.ref import (apply_gate_planes_ref,
                                                 apply_layer_planes_ref)
    from repro.kernels.swa_attention.ops import _fold, _unfold
    from repro.kernels.swa_attention.ref import swa_attention_ref
    from repro.quantum import statevector as sv

    key = jax.random.key(seed)
    k = lambda i: jax.random.fold_in(key, i)                 # noqa: E731
    bits = lambda i, *shape: jax.random.bits(k(i), shape, jnp.uint32)  # noqa

    def state(i, shape):
        re, im = jax.random.normal(k(i), (2,) + shape)
        s = (re + 1j * im).astype(jnp.complex64)
        return s / jnp.linalg.norm(s, axis=-1, keepdims=True)

    def gates(i, nq):
        a = jax.random.uniform(k(i), (3, nq), minval=-3.0, maxval=3.0)
        return sv.u3_gate(a[0], a[1], a[2])

    def planes(ref, s, *a):
        re, im = ref(jnp.real(s), jnp.imag(s), *a)
        return (re + 1j * im).astype(jnp.complex64)

    cases = [
        ("otp_xor_mac 16384 words",
         lambda m, p, r, s: otp_xor_mac(m, p, r, s, interpret=False),
         otp_xor_mac_ref, (bits(1, 16384), bits(2, 16384), bits(3), bits(4)),
         0),
        ("otp_xor_mac_edges 32 x 16384",
         lambda m, p, r, s: otp_xor_mac_edges(m, p, r, s, interpret=False),
         jax.vmap(otp_xor_mac_ref),
         (bits(5, 32, 16384), bits(6, 32, 16384), bits(7, 32), bits(8, 32)),
         0),
    ]
    for q in (5, 11):
        cases.append((
            f"apply_gate nq=12 q={q}",
            lambda s, g, q=q: apply_gate(s, g, q, interpret=False),
            lambda s, g, q=q: planes(apply_gate_planes_ref, s, _pack_gate(g),
                                     q),
            (state(9, (4096,)), gates(10, 1)[0]), GATE_ATOL))
    for shape in ((4096,), (8, 4096), (1 << 20,)):
        nq = shape[-1].bit_length() - 1
        cases.append((
            f"apply_gate_layer {shape}",
            lambda s, g: apply_gate_layer(s, g, interpret=False),
            lambda s, g: planes(apply_layer_planes_ref, s, _pack_gates(g)),
            (state(11 + nq, shape), gates(12 + nq, nq)), GATE_ATOL))

    cases.append((
        "swa_attention S=512 W=128",
        lambda q, kk, v: swa_attention(q, kk, v, window=128,
                                       interpret=False),
        lambda q, kk, v: _unfold(swa_attention_ref(
            _fold(q), _fold(kk), _fold(v), window=128), 2, 4),
        tuple(0.3 * jax.random.normal(k(40 + i), (2, 512, 4, 64))
              for i in range(3)), SWA_ATOL))

    hm = lambda t: jnp.moveaxis(t, 2, 1)   # (B,S,H,.) <-> (B,H,S,.) # noqa

    def ssd_oracle(x, dt, A, Bv, Cv):
        y, st = ssd_scan_ref(hm(x), hm(dt[..., None])[..., 0], A, hm(Bv),
                             hm(Cv), chunk=128)
        return hm(y), st

    cases.append((
        "ssd_scan S=512",
        lambda *a: ssd_scan(*a, chunk=128, interpret=False), ssd_oracle,
        (0.3 * jax.random.normal(k(50), (1, 512, 4, 32)),
         jax.nn.softplus(jax.random.normal(k(51), (1, 512, 4))),
         -jnp.exp(0.3 * jax.random.normal(k(52), (4,))),
         0.3 * jax.random.normal(k(53), (1, 512, 1, 32)),
         0.3 * jax.random.normal(k(54), (1, 512, 1, 32))), SSD_ATOL))
    return cases


def kernels_phase(seed: int, platform: str = "tpu"):
    import jax
    import numpy as np

    for name, kern, ref, args, atol in kernel_cases(seed):
        kern = jax.jit(kern)
        t = time.perf_counter()
        got = jax.block_until_ready(kern(*args))
        setup_s = time.perf_counter() - t
        t = time.perf_counter()
        jax.block_until_ready(kern(*args))
        call_ms = (time.perf_counter() - t) * 1e3
        # the oracle's matmuls at full f32, so only the kernel is on trial
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(ref)(*args))
        if atol == 0:
            ok = all(np.array_equal(np.asarray(a), np.asarray(b))
                     for a, b in zip(leaves(got), leaves(want)))
            err = "bit-exact" if ok else "MISMATCH"
        else:
            d = max_abs_diff(got, want)
            ok, err = d <= atol, f"max diff {d:.3e} (atol {atol:g})"
        print(f"  smoke timing {name}: setup {setup_s:.2f} s, "
              f"{call_ms:.3f} ms/call", flush=True)
        check(ok and on_platform(got, platform),
              f"kernel {name} vs ref.py: {err}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dist round sharded over 4 chips")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {devs}); nothing "
              f"was run", file=sys.stderr)
        return 3
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: {need} TPU chips needed, found {len(devs)}",
              file=sys.stderr)
        return 3
    print(f"chip_smoke: {devs[0].device_kind} x {len(devs)}, jax "
          f"{jax.__version__}, compile cache {cache_dir}", flush=True)

    t_all = time.perf_counter()
    if args.four_chips:
        phase("four chips: sharded dist round", four_chip_phase, args.seed)
    else:
        w = phase("workload", workload, args.seed)
        if w is not None:
            sim = None
            for mode, extra in HOST_PHASES:
                name = "host " + mode + "".join(
                    f" {k}={v}" for k, v in extra.items())
                out = phase(name, host_phase, w, mode, extra)
                if out is not None and sim is None and mode == "sim":
                    sim = out
            if sim is not None:
                phase("oracle: sim per-client and cpu", oracle_phase, w,
                      sim[1])
            phase("dist: make_fl_round sim otp", dist_phase, w)
        phase("kernels: interpret=False vs ref.py", kernels_phase, args.seed)
    print(f"chip_smoke: {time.perf_counter() - t_all:.1f} s in all",
          flush=True)

    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed:", file=sys.stderr)
        for f in FAILED:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
