"""The host-trainer engine: ``SatQFLTrainer.run_round`` round after round.

Set-up builds one trainer from the cell's configuration, traffic mix and
seed, gives it the benchmark's weights, and drives its first rounds (the
rounds the reference checks, and any further warm-up the mix asks for).
The window then drives the same object on from the next round.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import numpy as np

from harness import flops, inputs, reference


class Engine:
    def __init__(self, config: dict, traffic: dict, seed: int, trace=None):
        from repro.core import SatQFLConfig, SatQFLTrainer
        from repro.models import get_model

        self.config, self.traffic, self.seed = config, traffic, seed
        self.parts = {}
        t = time.perf_counter()
        self.sats, self.server = inputs.make_data(config, seed)
        self.parts["data_s"] = time.perf_counter() - t

        t = time.perf_counter()
        self.trace = trace or inputs.build_constellation(config, traffic)
        self.parts["trace_s"] = time.perf_counter() - t

        t = time.perf_counter()
        cfg = inputs.model_config(config)
        fl = dict(config["fl"])
        fl.update(traffic.get("fl", {}))
        self.fl = fl
        # fl["seed"] is the configuration's: the program compiles the key
        # it makes from it into its training stage, so a seed of its own
        # per run would compile that stage anew in every run
        self.fl_cfg = SatQFLConfig(n_rounds=traffic["plan_rounds"], **fl)
        self.trainer = SatQFLTrainer(
            cfg, get_model(cfg), self.fl_cfg, self.trace, self.sats,
            self.server, batched=True, edge_batched=True)
        # the key is an argument, so every seed shares one program
        self.params0 = jax.jit(partial(reference.init_params,
                                       config["model"]))(
            reference.init_key(seed))
        self.trainer.global_params = self.params0
        self.parts["trainer_s"] = time.perf_counter() - t
        self.plan_rounds = traffic["plan_rounds"]
        self.next_round = 0
        self.snapshots, self.losses = [], []

    # -- the rounds ---------------------------------------------------------
    def round(self, r: int) -> bool:
        """Run round r; True when it completed with a finite loss."""
        m = self.trainer.run_round(r)
        jax.block_until_ready(self.trainer.global_params)
        return math.isfinite(m.server_val_loss)

    def warm_up(self, n_check: int, rounds: int | None = None):
        """The first rounds (the mix's ``warmup_rounds``, at least the
        checked ones): the checked ones keep host copies of the global
        model and the server's loss."""
        t = time.perf_counter()
        for r in range(max(n_check, rounds or self.traffic["warmup_rounds"])):
            t_r = time.perf_counter()
            self.round(r)
            self.parts[f"warmup_round{r}_s"] = time.perf_counter() - t_r
            if r < n_check:
                self.snapshots.append(jax.tree_util.tree_map(
                    np.asarray, self.trainer.global_params))
                self.losses.append(self.trainer.history[-1].server_val_loss)
        self.next_round = r + 1
        self.parts["warmup_s"] = time.perf_counter() - t

    def rounds_left(self) -> int:
        return self.plan_rounds - self.next_round

    def step(self) -> bool:
        r = self.next_round
        self.next_round += 1
        return self.round(r)

    def model_flops(self, first: int, n: int) -> int:
        """Model FLOPs of rounds [first, first + n): every secondary of a
        group and every main trains (the program's own plan says who), and
        each round evaluates the server's validation and test rows and the
        first 64 rows (or fewer, where a satellite has fewer) of the first
        8 satellites."""
        plan = self.trainer.plan
        evals = (len(self.server["val"]["labels"])
                 + len(self.server["test"]["labels"])
                 + sum(min(64, len(d["labels"])) for d in self.sats[:8]))
        return sum(flops.round_flops(
            self.config["model"], self.fl,
            sum(1 + len(s) for s in plan.live_groups(r).values()), evals)
            for r in range(first, first + n))

    def free(self):
        self.trainer = None

    # -- the reference --------------------------------------------------------
    def reference(self, n_check: int, prec: str, keep: float = 1.0,
                  stats: dict | None = None):
        """(global params, server validation loss) after each of the first
        ``n_check`` rounds, by the plain reference at ``prec``."""
        model = self.config["model"]
        params = reference.run_rounds(
            model=model, fl=self.fl, trace=self.trace,
            plan_rounds=self.plan_rounds, sats=self.sats,
            params0=self.params0, seed=self.fl["seed"], n_rounds=n_check,
            prec=prec, keep=keep, stats=stats)
        return params, [reference.val_loss(model, p, self.server, prec)
                        for p in params]
