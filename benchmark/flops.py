"""Floating-point operations one training step must do in the dense head,
from shapes alone: the numerator of ``deep_head_mxu_roofline``.

Only the matrix products count (the MXU's work): per example and per
weight one multiply-add forward, one for the gradient with respect to
the layer's input and one for the gradient with respect to the weight,
2 operations each. The first layer's input gradient counts too: the
embedding is trained, so the step needs it. Biases, ReLUs and Adam are
elementwise and left out, as are the extra passes a float32 product
costs on a bfloat16 MXU: the share says how far the head as built is
from the chip's peak, not what its precision costs.
"""

from __future__ import annotations


def head_weights(dims) -> int:
    """Kernel elements of a dense stack of widths ``dims``."""
    return sum(d_in * d_out for d_in, d_out in zip(dims[:-1], dims[1:]))


def head_matmul_flops(batch: int, dims) -> int:
    """Operations of one step (forward and backward) over ``batch``
    examples: ``6 * batch * weights``."""
    return 6 * batch * head_weights(dims)
