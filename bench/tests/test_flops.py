"""The operation counts the utilisation and roofline metrics divide by,
against counts made by hand from the published layer lists."""
import pytest

from bench.harness import core, flops


def _hand_vgg11():
    # (spatial side, c_in, c_out) of VGG-11 configuration A on 32x32x3
    convs = [(32, 3, 64), (16, 64, 128), (8, 128, 256), (8, 256, 256),
             (4, 256, 512), (4, 512, 512), (2, 512, 512), (2, 512, 512)]
    return sum(2 * s * s * 9 * ci * co for s, ci, co in convs) + 2 * 512 * 10


def _hand_mnist():
    return (2 * 28 * 28 * 9 * 1 * 32 + 2 * 14 * 14 * 9 * 32 * 64
            + 2 * 7 * 7 * 64 * 128 + 2 * 128 * 10)


@pytest.mark.parametrize("name, fwd, params, approx_mflop", [
    ("vgg11-cifar10", _hand_vgg11(), 9_225_610, 305.5),
    ("mnist-cnn", _hand_mnist(), 421_642, 8.48),
])
def test_counts_match_the_hand_counts(name, fwd, params, approx_mflop):
    cfg = core.config(name)
    assert flops.forward_flops(cfg) == fwd
    assert flops.train_flops(cfg) == 3 * fwd
    assert flops.forward_flops(cfg) / 1e6 == pytest.approx(approx_mflop,
                                                           abs=0.05)
    assert flops.param_count(cfg) == params == cfg["param_count"]


def test_vgg11_train_step_is_916_6_mflop():
    cfg = core.config("vgg11-cifar10")
    assert flops.train_flops(cfg) / 1e6 == pytest.approx(916.6, abs=0.05)


def test_local_update_cost_counts_every_slot_and_the_models():
    cfg = core.config("mnist-cnn")
    ops, byts = flops.local_update_cost(cfg, [(4, 5, 32), (2, 5, 64)])
    assert ops == (4 * 5 * 32 + 2 * 5 * 64) * flops.train_flops(cfg)
    model = 4 * 421_642
    sample = 4 * 28 * 28
    assert byts == (4 * 5 * (2 * model + 32 * sample)
                    + 2 * 5 * (2 * model + 64 * sample))
    assert flops.aggregate_bytes(cfg, 68) == 69 * model
