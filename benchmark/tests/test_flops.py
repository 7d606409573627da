"""The head's matmul operations against a count by hand."""

from benchmark import flops
from benchmark.reference import deepfm


def test_head_weights_and_flops_of_config_5():
    dims = deepfm.head_dims(39, 16, [400, 400, 400])
    assert dims == (624, 400, 400, 400, 1)
    # 624 x 400 + 400 x 400 + 400 x 400 + 400 x 1 kernel elements.
    assert flops.head_weights(dims) == 249_600 + 160_000 + 160_000 + 400
    assert flops.head_weights(dims) == 570_000
    # Per example and weight: a multiply-add forward, one for the
    # input's gradient, one for the weight's: 3 x 2 operations.
    assert flops.head_matmul_flops(16_384, dims) == 6 * 16_384 * 570_000
    assert flops.head_matmul_flops(16_384, dims) == 56_033_280_000


def test_one_layer_by_hand():
    # [2, 3] @ [3, 5]: forward 2*3*5 multiply-adds, and as many for each
    # of the two gradients.
    assert flops.head_weights((3, 5)) == 15
    assert flops.head_matmul_flops(2, (3, 5)) == 3 * 2 * (2 * 3 * 5)
