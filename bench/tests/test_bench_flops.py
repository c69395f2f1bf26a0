"""The model-FLOP count by hand, and the peak table."""
import pytest

from harness import flops

# 2 qubits, 1 layer, 2 features, 2 classes: dim 4, 2 encoding gates and
# 2 x 2 ansatz rotations, 2 CZs
MODEL = {"vqc_qubits": 2, "vqc_layers": 1, "n_features": 2, "n_classes": 2}
FORWARD = (14 * 4 * 6        # six 1-qubit gates
           + 2 * 4 * 2       # two CZs
           + 3 * 4           # probabilities
           + 2 * 4 * 2       # <Z> of two classes
           + 8 * 2)          # head and cross-entropy


def test_forward_by_hand():
    assert FORWARD == 396
    assert flops.forward_flops(MODEL) == FORWARD


@pytest.mark.parametrize("rule,passes", [("autodiff", 3),
                                         ("param_shift", 1 + 2 * 4)])
def test_round_flops_by_gradient_rule(rule, passes):
    fl = {"local_steps": 5, "batch_size": 3, "grad_method": rule}
    # 2 satellites x 5 steps x 3 rows, and 7 evaluation rows
    assert flops.round_flops(MODEL, fl, trained=2, eval_rows=7) == \
        FORWARD * (2 * 5 * 3 * passes + 7)


def test_unknown_device_kind_raises():
    assert flops.peak("TPU v5 lite")["bf16_flops_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v99")
