"""TPU compute kernels: the FM/FFM forward-backward math.

This package is the rebuild of the reference's per-example
``computeGradient`` hot loop (BASELINE.json:5 — "the order-2 pairwise
interaction term and its latent-factor gradient"), lifted from a per-example
Scala loop into batched, jit-compiled JAX over gathered embedding rows.
"""


class PallasUnavailable(ValueError):
    """A Pallas kernel cannot serve this (backend, shape, dtype) request.

    The STRUCTURED fallback signal of the kernel tier (ISSUE 8): every
    ``ops/pallas_*.py`` module raises exactly this — never a bare
    ``assert`` — when a hardware constraint (Mosaic lane alignment, the
    scalar-prefetch SMEM budget, the VMEM residency budget) or a missing
    Pallas lowering makes the kernel unusable, so callers holding an
    ``auto`` lever (``TrainConfig.fused_embed='auto'``) can catch it and
    degrade to the XLA path instead of dying mid-attachment
    (tools/resilience_lint.py enforces the no-assert rule). Subclasses
    ``ValueError`` so pre-existing callers pinning ``ValueError`` keep
    working.
    """


def pallas_interpret() -> bool:
    """THE interpret-mode decision for every Pallas kernel in the repo:
    ``cpu`` interprets (tests, dry runs), ``tpu`` compiles through
    Mosaic, and any other platform raises — a kernel that silently
    interpreted on an accelerator nobody named would be timed as if it
    were the real thing."""
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise PallasUnavailable(
        f"Pallas kernels here compile for 'tpu' and interpret on 'cpu'; "
        f"the default backend is {platform!r}, which is neither")


from fm_spark_tpu.ops.fm import (  # noqa: F401,E402
    fm_scores,
    fm_partial_terms,
    fm_scores_from_partials,
    fm_scores_dense,
)
from fm_spark_tpu.ops.ffm import ffm_scores, ffm_scores_dense  # noqa: F401,E402
from fm_spark_tpu.ops.losses import (  # noqa: F401,E402
    logistic_loss,
    squared_loss,
    loss_fn,
)
