"""Model families: FM, FFM, DeepFM.

Parity targets (SURVEY.md §2): the reference ships `FMModel` (+`FMWithSGD`)
and an FFM config; DeepFM is the stretch config requiring a new nn head
(BASELINE.json:10-11). Each model here is a frozen spec dataclass + pure
``init`` / ``scores`` / ``predict`` functions over a param pytree — the
idiomatic JAX shape of the reference's model classes.
"""

from fm_spark_tpu.models.base import ModelSpec, predict_from_scores  # noqa: F401
from fm_spark_tpu.models.fm import FMSpec  # noqa: F401
from fm_spark_tpu.models.ffm import FFMSpec  # noqa: F401
from fm_spark_tpu.models.deepfm import DeepFMSpec  # noqa: F401
from fm_spark_tpu.models.field_dcn import FieldDCNSpec  # noqa: F401
from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec  # noqa: F401
from fm_spark_tpu.models.field_dlrm import FieldDLRMSpec  # noqa: F401
from fm_spark_tpu.models.field_fm import FieldFMSpec  # noqa: F401
from fm_spark_tpu.models.field_ffm import FieldFFMSpec  # noqa: F401
from fm_spark_tpu.models.field_xdeepfm import FieldXDeepFMSpec  # noqa: F401
from fm_spark_tpu.models.io import save_model, load_model  # noqa: F401
from fm_spark_tpu.models.libfm_io import save_libfm, load_libfm  # noqa: F401


def build(spec):
    """Return the model functions for a spec: ``(init, scores)``."""
    return spec.init, spec.scores
