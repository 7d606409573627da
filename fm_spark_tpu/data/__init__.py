"""Data layer: hashing, dataset parsers, packed binary format, loader.

The reference's L2 is ``RDD[LabeledPoint]`` with sparse one-hot vectors fed
by ``MLUtils.loadLibSVMFile`` plus an upstream hashing step for Criteo/Avazu
(SURVEY.md §2 row 7, §3.3). Here the canonical in-memory encoding is the
fixed-nnz triple ``(ids int32 [N, nnz], vals float32 [N, nnz], labels
float32 [N])`` — the shape the kernels and XLA want.
"""

from fm_spark_tpu.data.synthetic import synthetic_ctr  # noqa: F401
from fm_spark_tpu.data.pipeline import (  # noqa: F401
    Batches,
    BernoulliBatches,
    DedupAuxBatches,
    MappedBatches,
    PlacedBatches,
    Prefetcher,
    StackedBatches,
    iterate_once,
    train_test_split,
    wrap_prefetch,
)
from fm_spark_tpu.data.packed import (  # noqa: F401
    PackedBatches,
    PackedDataset,
    PackedWriter,
    shuffle_packed,
)
from fm_spark_tpu.data.libsvm import load_libsvm, save_libsvm  # noqa: F401
from fm_spark_tpu.data.stream import (  # noqa: F401
    BadRecord,
    IngestAborted,
    RecordGuard,
    ShardReader,
    StreamBatches,
    line_parser,
)
from fm_spark_tpu.data.native_stream import (  # noqa: F401
    NativeStreamBatches,
    make_stream_batches,
    native_stream_supported,
)
