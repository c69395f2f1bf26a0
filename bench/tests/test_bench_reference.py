"""The plain reference against the program's trainer: two rounds of sim
with QKD-keyed OTP+MAC, and six of async with secure aggregation, on the
tiny configuration, on the CPU."""
import json

import jax
import numpy as np
import pytest

from conftest import write_tiny_root

from harness import check, spec
from harness.host import Engine


# async needs 6 rounds before an update arrives at the tiny size
@pytest.mark.parametrize("traffic,rounds", [("sim.qkd", 2),
                                            ("async.secagg", 6)])
def test_reference_follows_trainer(tmp_path, traffic, rounds):
    root = write_tiny_root(tmp_path)
    cell = spec.load_cell(traffic, root)
    engine = Engine(cell.config, cell.traffic, 2147483901)
    engine.warm_up(rounds, rounds=rounds)
    covered = {}
    ref, losses = engine.reference(rounds, "highest", stats=covered)
    assert covered["merged"] > 0 and covered["mains"] > 0, covered
    # the program moves exactly the leaves the reference moves (the
    # one-layer circuit's RZ angles commute with the Z readout)
    for k in engine.params0:
        p0 = np.asarray(engine.params0[k])
        assert (np.abs(engine.snapshots[-1][k] - p0).max() > 0) == \
            (np.abs(np.asarray(ref[-1][k]) - p0).max() > 0), k
    nums = check.numbers(engine.params0, engine.snapshots, engine.losses,
                         ref, losses)
    assert check.verdict(nums, cell.traffic["limits"]), nums
    for a, b in zip(jax.tree_util.tree_leaves(engine.snapshots[-1]),
                    jax.tree_util.tree_leaves(ref[-1])):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
    json.dumps(nums)
