"""A cell's inputs from its configuration, its traffic mix and ``--seed``.

Data: a synthetic stand-in for Statlog (Landsat) with the published
cardinalities (6,435 samples, 36 raw features, 7 classes), reduced to the
VQC's feature count and scaled to [0, pi] for angle encoding; 10% held at
the server, the rest split over the satellites by a Dirichlet label skew.
The generator is the benchmark's own copy, so a change to the program's
data helpers cannot move the yardstick.

Constellation: the configuration's Walker shell propagated over the trace
the traffic mix asks for. It is the deployment, not an input, so it does
not change with the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def class_mixture(n_samples: int, n_raw: int, n_classes: int,
                  n_features: int, seed: int):
    """Gaussian class mixture -> (features (n, n_features) in [0, pi],
    labels (n,)) as float32 / int32 numpy arrays."""
    rng = np.random.default_rng(seed)
    means = rng.normal(0, 1.6, (n_classes, n_raw))
    mix = rng.normal(0, 1.0, (n_raw, n_raw))
    labels = rng.integers(0, n_classes, n_samples)
    x = means[labels] + rng.normal(0, 1.0, (n_samples, n_raw)) @ mix * 0.45
    q, _ = np.linalg.qr(rng.normal(0, 1, (n_raw, n_raw)))
    z = x @ q[:, :n_features]
    lo, hi = z.min(axis=0), z.max(axis=0)
    z = (z - lo) / np.maximum(hi - lo, 1e-9) * np.pi
    return z.astype(np.float32), labels.astype(np.int32)


def server_split(x, y, server_frac: float, seed: int):
    n = x.shape[0]
    perm = np.random.default_rng(seed).permutation(n)
    n_srv = int(n * server_frac)
    srv, cli = perm[:n_srv], perm[n_srv:]
    half = n_srv // 2
    server = {"val": {"features": x[srv[:half]], "labels": y[srv[:half]]},
              "test": {"features": x[srv[half:]], "labels": y[srv[half:]]}}
    return x[cli], y[cli], server


def dirichlet_partition(x, y, n_clients: int, alpha: float, seed: int):
    """Non-IID label skew; every client's rows repeated or cut to the mean
    share of the rows, so every client, and every seed, has the same
    shapes (one compiled program for all seeds)."""
    n_classes = int(y.max()) + 1
    rng = np.random.default_rng(seed)
    idx_by_class = [np.where(y == c)[0] for c in range(n_classes)]
    for idx in idx_by_class:
        rng.shuffle(idx)
    client_idx = [[] for _ in range(n_clients)]
    for idx in idx_by_class:
        props = rng.dirichlet([alpha] * n_clients)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for cl, part in enumerate(np.split(idx, cuts)):
            client_idx[cl].extend(part.tolist())
    target = len(y) // n_clients
    out = []
    for ci in client_idx:
        ci = np.array(ci if ci else rng.integers(0, len(y), 1))
        ci = np.tile(ci, int(np.ceil(target / len(ci))))[:target]
        out.append({"features": x[ci], "labels": y[ci]})
    return out


def make_data(config: dict, seed: int):
    """(per-satellite list of {features, labels}, server {val, test}),
    numpy, from the configuration's data section and the seed."""
    d, m = config["data"], config["model"]
    x, y = class_mixture(d["n_samples"], d["n_raw_features"],
                         m["n_classes"], m["n_features"], seed)
    xc, yc, server = server_split(x, y, d["server_frac"], seed)
    sats = dirichlet_partition(xc, yc, config["constellation"]["n_sats"],
                               d["dirichlet_alpha"], seed)
    return sats, server


def model_config(config: dict):
    """The program's ArchConfig for the configuration's model, with every
    size the file states set from the file."""
    from repro.models import get_config
    m = config["model"]
    base = get_config(m["arch"])
    return dataclasses.replace(
        base, vqc_qubits=m["vqc_qubits"], vqc_layers=m["vqc_layers"],
        n_layers=m["vqc_layers"], n_features=m["n_features"],
        n_classes=m["n_classes"], dtype=m["dtype"])


def trace_seconds(config: dict, traffic: dict) -> float:
    """Trace length that holds ``plan_rounds`` rounds at the traffic's
    cadence of ``steps_per_round`` trace steps."""
    step = config["constellation"]["step_s"]
    return traffic["plan_rounds"] * traffic["steps_per_round"] * step


def build_constellation(config: dict, traffic: dict):
    import inspect

    from repro.constellation import build_trace
    from repro.constellation.orbits import (GROUND_STATIONS,
                                            walker_constellation)
    c = config["constellation"]
    shell = inspect.signature(walker_constellation).parameters
    if (shell["inc_deg"].default, shell["alt_km"].default,
            len(GROUND_STATIONS)) != (c["inc_deg"], c["alt_km"],
                                      c["ground_stations"]):
        raise ValueError("the configuration's shell or ground stations "
                         "differ from what build_trace propagates")
    return build_trace(n_sats=c["n_sats"], n_planes=c["n_planes"],
                       duration_s=trace_seconds(config, traffic),
                       step_s=c["step_s"], min_elev_deg=c["min_elev_deg"])
