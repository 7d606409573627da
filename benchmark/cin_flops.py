"""Floating-point operations one xDeepFM training step must do in its
Compressed Interaction Network's products, from shapes alone: the
numerator of ``cin_mxu_roofline``.

Layer k compresses ``B x D`` rows of ``H_{k-1} x m`` Hadamard products
into ``H_k`` feature maps: per example, per embedding column and per
kernel element one multiply-add forward, one for the gradient with respect
to the products (the layer's inputs, ``X^{k-1}`` and ``X^0``, stand behind
them) and one for the kernel's gradient, 2 operations each:
``6 x B x D x sum_k H_{k-1} m H_k``. The Hadamard products, the pooling
and the output weight are elementwise or negligible and left out, as are
the extra passes a float32 product costs on a bfloat16 MXU: the share says
how far the CIN as built is from the chip's peak, not what its precision
costs (six passes: it cannot read over a sixth).
"""

from __future__ import annotations


def cin_weights(fields: int, cin_layers) -> int:
    """Kernel elements of the CIN: ``sum_k H_{k-1} m H_k``, ``H_0 = m``."""
    dims = (fields, *cin_layers)
    return sum(a * fields * b for a, b in zip(dims[:-1], dims[1:]))


def step_matmul_flops(batch: int, fields: int, rank: int, cin_layers) -> int:
    """Operations of one step's CIN products (forward and backward) over
    ``batch`` examples of ``fields`` embeddings ``rank`` wide."""
    return 6 * batch * rank * cin_weights(fields, cin_layers)


def outer_elems(batch: int, fields: int, rank: int, cin_layers) -> int:
    """Elements of the Hadamard-product blocks one forward builds: ``B x D
    x sum_k H_{k-1} m``."""
    dims = (fields, *cin_layers)
    return batch * rank * sum(h * fields for h in dims[:-1])
