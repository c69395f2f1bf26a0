"""Compile for a described TPU v5e, without the chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described rather than attached. Every Pallas kernel entry
compiles here with ``interpret=False`` (a ``tpu_custom_call`` must be in
the program), and so does the in-graph FL round, on one chip and with
the satellite axis sharded over a 4-chip mesh. Nothing runs: a compile
that passes says nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core import SatQFLConfig
from repro.core.dist import (fl_init_state, fl_input_specs, make_fl_round,
                             sat_axis_shardings)
from repro.kernels import (apply_gate, apply_gate_layer, otp_xor_mac,
                           otp_xor_mac_edges, ssd_scan, swa_attention)
from repro.models import get_config, get_model
from repro.nn.optim import sgd

U32, F32, C64 = jnp.uint32, jnp.float32, jnp.complex64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:        # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


KERNELS = {
    "otp_xor_mac 16384": (
        lambda m, p: otp_xor_mac(m, p, U32(3), U32(5), interpret=False),
        [((16384,), U32)] * 2),
    "otp_xor_mac_edges 32x16384": (
        lambda m, p, r, s: otp_xor_mac_edges(m, p, r, s, interpret=False),
        [((32, 16384), U32)] * 2 + [((32,), U32)] * 2),
    "apply_gate nq=12 q=5": (
        lambda s, g: apply_gate(s, g, 5, interpret=False),
        [((4096,), C64), ((2, 2), C64)]),
    "apply_gate nq=14 q=13": (
        lambda s, g: apply_gate(s, g, 13, interpret=False),
        [((1 << 14,), C64), ((2, 2), C64)]),
    "apply_gate_layer 2^12": (
        lambda s, g: apply_gate_layer(s, g, interpret=False),
        [((4096,), C64), ((12, 2, 2), C64)]),
    "apply_gate_layer 8x2^12": (
        lambda s, g: apply_gate_layer(s, g, interpret=False),
        [((8, 4096), C64), ((12, 2, 2), C64)]),
    "apply_gate_layer 50x2^4": (
        lambda s, g: apply_gate_layer(s, g, interpret=False),
        [((50, 16), C64), ((4, 2, 2), C64)]),
    "apply_gate_layer 2^20": (
        lambda s, g: apply_gate_layer(s, g, interpret=False),
        [((1 << 20,), C64), ((20, 2, 2), C64)]),
    "apply_gate_layer 2^15 per-gate fallback": (
        lambda s, g: apply_gate_layer(s, g, group_tile=3, interpret=False),
        [((1 << 15,), C64), ((15, 2, 2), C64)]),
    "swa_attention S=512 W=128": (
        lambda q, k, v: swa_attention(q, k, v, window=128, interpret=False),
        [((2, 512, 4, 64), F32)] * 3),
    "ssd_scan S=512": (
        lambda x, dt, A, B, C: ssd_scan(x, dt, A, B, C, chunk=128,
                                        interpret=False),
        [((1, 512, 4, 32), F32), ((1, 512, 4), F32), ((4,), F32),
         ((1, 512, 1, 32), F32), ((1, 512, 1, 32), F32)]),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert "tpu_custom_call" in _compile(fn, args)


def _fl_round_specs(n_sats: int, sharding_of):
    """(round_fn, argument specs) for make_fl_round (sim, otp) on the
    paper's VQC; ``sharding_of(tree)`` gives each spec its sharding."""
    cfg = get_config("vqc-satqfl")
    api = get_model(cfg)
    fl = SatQFLConfig(mode="sim", security="qkd")
    opt = sgd(fl.lr)
    round_fn = make_fl_round(cfg, api, fl, opt, n_sats, security="otp")
    state = jax.eval_shape(
        lambda k: fl_init_state(cfg, api, opt, n_sats, k),
        jax.random.PRNGKey(0))
    ins = fl_input_specs(cfg, api, fl, n_sats, (cfg.n_features,),
                         cfg.n_classes)
    args = (state, ins["batches"], ins["part_mask"], ins["seeds"],
            ins["weights"])
    placed = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        args, sharding_of(args))
    return round_fn, placed


def test_fl_round_compiles_for_one_v5e_chip(one_chip):
    round_fn, args = _fl_round_specs(
        48, lambda t: jax.tree_util.tree_map(lambda _: one_chip, t))
    hlo = _compile(round_fn, args)
    assert "all-reduce" not in hlo


def test_fl_round_compiles_sharded_over_4_chips(topo):
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    round_fn, args = _fl_round_specs(
        48, lambda t: sat_axis_shardings(t, mesh))
    hlo = _compile(round_fn, args)
    # FedAvg over the sharded satellite axis needs a cross-chip reduction
    assert "all-reduce" in hlo
