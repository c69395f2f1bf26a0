"""Training driver.

Two entry modes:

  * standard LM pretraining on the synthetic corpus (any --arch; --smoke
    uses the reduced config so it runs on this CPU container):

      PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
          --smoke --steps 50 --batch 8 --seq 128

  * sat-QFL federated training (--fl): the in-graph stacked-satellite round
    (repro.core.dist) over the host mesh — the small-scale twin of the
    production FL dry-run:

      PYTHONPATH=src python -m repro.launch.train --fl --mode sim \
          --security secagg --rounds 5

    --engine host runs the host-orchestrated trainer instead (full comm
    model + Algorithm 2 security) with the constellation-batched executor;
    --engine host-perclient selects its per-client numerics oracle:

      PYTHONPATH=src python -m repro.launch.train --fl --engine host \
          --mode sim --rounds 5 --sats 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def run_lm(args):
    from repro.data.tokens import lm_batches, synthetic_corpus
    from repro.models import get_config, get_model, smoke_variant
    from repro.nn.optim import get_optimizer, cosine_schedule

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    api = get_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = api.init(cfg, key)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    print(f"[train] {cfg.name} ({'smoke' if args.smoke else 'full'}): "
          f"{n_params/1e6:.1f}M params")

    opt = get_optimizer(args.optimizer,
                        cosine_schedule(args.lr, args.steps, warmup=10))
    opt_state = opt.init(params)

    corpus = synthetic_corpus(max(args.batch * args.seq * 50, 100_000),
                              cfg.vocab_size, seed=args.seed)

    def extras(batch_size):
        out = {}
        if cfg.family == "encdec":
            out["audio_embeds"] = 0.02 * jax.random.normal(
                jax.random.PRNGKey(1),
                (batch_size, cfg.n_audio_frames, cfg.d_model))
        if cfg.family == "vlm":
            out["image_embeds"] = 0.02 * jax.random.normal(
                jax.random.PRNGKey(2),
                (batch_size, cfg.n_image_tokens, cfg.d_model))
        return out

    @jax.jit
    def step(params, opt_state, batch, n):
        loss, g = jax.value_and_grad(
            lambda p: api.loss(cfg, p, batch))(params)
        params, opt_state = opt.update(g, opt_state, params, n)
        return params, opt_state, loss

    mgr = None
    start = 0
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.ckpt_dir, keep=2)
        if mgr.latest is not None:
            (params, opt_state), start, _ = mgr.restore((params, opt_state))
            print(f"[train] resumed from step {start}")

    ex = extras(args.batch)
    t0 = time.time()
    losses = []
    for i, batch in enumerate(lm_batches(corpus, args.batch, args.seq,
                                         args.steps, seed=args.seed)):
        if i < start:
            continue
        batch.update(ex)
        params, opt_state, loss = step(params, opt_state, batch,
                                       jnp.asarray(i, jnp.int32))
        losses.append(float(loss))
        if i % max(args.steps // 10, 1) == 0:
            print(f"  step {i:4d}  loss {losses[-1]:.4f}  "
                  f"({(time.time()-t0):.1f}s)")
        if mgr and (i + 1) % max(args.steps // 3, 1) == 0:
            mgr.save(i + 1, (params, opt_state), {"loss": losses[-1]})
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"({'improved' if losses[-1] < losses[0] else 'NO IMPROVEMENT'})")
    return losses


def run_fl_host(args, cfg, api, fl, trace, sats, server):
    """Host-orchestrated engine (comm model + Algorithm 2 security) —
    constellation-batched executor by default, per-client oracle via
    --engine host-perclient."""
    import time as _time

    from repro.core import SatQFLTrainer

    batched = args.engine == "host"
    tr = SatQFLTrainer(cfg, api, fl, trace, sats, server, batched=batched)
    print(f"[fl] host engine ({'batched' if batched else 'per-client'}) "
          f"mode={fl.mode} security={fl.security} sats={tr.n_sats}")
    start = 0
    ckpt_dir = getattr(args, "ckpt_dir", None)
    if ckpt_dir:
        from repro.checkpoint.io import latest_step
        if latest_step(ckpt_dir) is not None:
            start = tr.restore_round_checkpoint(ckpt_dir)
            print(f"[fl] resumed from {ckpt_dir} at round {start}")
    for r in range(start, fl.n_rounds):
        t0 = _time.perf_counter()
        m = tr.run_round(r)
        print(f"  round {r}: val_loss={m.server_val_loss:.4f} "
              f"val_acc={m.server_val_acc:.3f} comm={m.comm_s:.2f}s "
              f"participants={m.participants} "
              f"({(_time.perf_counter() - t0) * 1e3:.0f} ms wall)")
        if ckpt_dir:
            tr.save_round_checkpoint(ckpt_dir)
    return tr


def run_fl(args):
    from repro.constellation import build_trace
    from repro.core import SatQFLConfig, compile_round_plan
    from repro.core.dist import (fl_init_state, make_fl_round,
                                 sample_round_batches, sat_axis_shardings,
                                 stack_client_data)
    from repro.launch.mesh import make_host_mesh
    from repro.data import make_statlog, dirichlet_partition, server_split
    from repro.models import get_config, get_model
    from repro.nn.optim import sgd

    cfg = get_config("vqc-satqfl")
    if args.qubits:
        cfg = cfg.replace(vqc_qubits=args.qubits, n_features=args.qubits)
    api = get_model(cfg)
    n_sats = args.sats
    if args.engine == "dist":
        # the in-graph engine takes its security mode directly in
        # make_fl_round; the config only needs a valid Algorithm-2 name
        # whose != "none" gate matches (plan key/seed compilation)
        security = "none" if args.security == "none" else "qkd"
    else:
        # host engine speaks Algorithm-2 mode names: the in-graph 'otp'
        # is the host's QKD-keyed OTP(+MAC); 'secagg' has no host
        # equivalent (masking is an in-graph construction) — reject it
        # rather than silently running unsecured
        host_map = {"none": "none", "otp": "qkd"}
        if args.security not in host_map:
            raise SystemExit(
                f"--security {args.security} is dist-engine only; the host "
                f"engine supports none|otp (otp runs as QKD-keyed OTP+MAC)")
        security = host_map[args.security]
    fl = SatQFLConfig(mode=args.mode, n_rounds=args.rounds,
                      local_steps=args.local_steps,
                      batch_size=args.batch, lr=args.lr, seed=args.seed,
                      security=security)
    X, y = make_statlog(n_features=cfg.n_features)
    Xc, yc, server = server_split(X, y)
    sats = dirichlet_partition(Xc, yc, n_sats)
    trace = build_trace(n_sats=n_sats, n_planes=max(n_sats // 2, 1),
                        duration_s=3600, step_s=60, seed=args.seed)
    if args.engine != "dist":
        return run_fl_host(args, cfg, api, fl, trace, sats, server)

    opt = sgd(fl.lr)
    state = fl_init_state(cfg, api, opt, n_sats, jax.random.PRNGKey(args.seed))
    data = stack_client_data(sats)
    n_dev = len(jax.devices())
    if n_dev > 1:
        # the stacked satellite axis is the data-parallel axis of the
        # host mesh: each device trains its slice of the constellation
        if n_sats % n_dev:
            raise SystemExit(f"--sats {n_sats} must be a multiple of the "
                             f"{n_dev} devices")
        mesh = make_host_mesh()
        state = jax.device_put(state, sat_axis_shardings(state, mesh))
        data = jax.device_put(data, sat_axis_shardings(data, mesh))
    seq_hops = 4
    round_fn = jax.jit(make_fl_round(cfg, api, fl, opt, n_sats,
                                     security=args.security,
                                     seq_hops=seq_hops))
    steps = fl.local_steps * (seq_hops if fl.mode == "seq" else 1)

    # participation masks, pad seeds and FedAvg weights all come from the
    # compiled constellation schedule — not invented here
    plan = compile_round_plan(
        trace, fl, sample_counts=[len(s["labels"]) for s in sats],
        with_seeds=(args.security != "none"))

    rng = np.random.default_rng(args.seed)
    print(f"[fl] mode={fl.mode} security={args.security} sats={n_sats} "
          f"devices={n_dev} "
          f"(plan: {plan.participants(0)}/{n_sats} participate at r0)")
    for r in range(args.rounds):
        batches = sample_round_batches(data, rng, steps, fl.batch_size)
        mask, seeds, weights = plan.dist_inputs(r)
        state, metrics = round_fn(state, batches, mask, seeds, weights)
        # server metrics on the aggregated model (satellite 0's copy)
        g_params = jax.tree_util.tree_map(lambda x: x[0], state.params)
        from repro.core.round import evaluate
        vl, va = evaluate(api, cfg, g_params, server["val"])
        print(f"  round {r}: local_loss={float(metrics['loss']):.4f} "
              f"val_loss={vl:.4f} val_acc={va:.3f}")
    return state


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (resume if present)")
    # FL mode
    ap.add_argument("--fl", action="store_true")
    ap.add_argument("--mode", default="sim", choices=["sim", "seq", "async", "qfl"])
    ap.add_argument("--engine", default="dist",
                    choices=["dist", "host", "host-perclient"],
                    help="dist = in-graph mesh round; host = paper-scale "
                         "trainer (constellation-batched); host-perclient "
                         "= its per-client numerics oracle")
    ap.add_argument("--security", default="none",
                    choices=["none", "otp", "secagg"])
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--sats", type=int, default=8)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--qubits", type=int, default=None,
                    help="override the VQC width (default: vqc-satqfl as "
                         "configured, 8 qubits)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.fl:
        run_fl(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()
