"""The benchmark's own traffic arithmetic: planted-FM CTR rows and the
Zipf id pool that scoring requests are cut from.

``synthetic_ctr`` is a copy of the program's generator
(``fm_spark_tpu/data/synthetic.py``): the training cells let ``cli train
--synthetic N --seed S`` draw its own rows, and the reference needs the
same rows without looking inside the program. The drivers compare
:func:`checksum` of a small seeded draw from both at set-up; if the
program's generator ever drifts from this copy, ``correct`` is false.
"""

from __future__ import annotations

import hashlib

import numpy as np

ZIPF_A = 1.5


def synthetic_ctr(num_examples: int, num_features: int, nnz: int,
                  rank: int = 4, seed: int = 0, scale: float = 1.5):
    """``(ids int32 [N, nnz], vals float32 [N, nnz], labels float32 [N])``
    from a planted FM: one Zipf(1.5) id per field bucket, label
    ~ Bernoulli(sigmoid(scale * standardized planted score))."""
    rng = np.random.default_rng(seed)
    if num_features < nnz:
        raise ValueError("num_features must be >= nnz (one feature per field)")
    bucket = num_features // nnz
    raw = rng.zipf(ZIPF_A, size=(num_examples, nnz)) % bucket
    ids = (raw + np.arange(nnz)[None, :] * bucket).astype(np.int32)
    vals = np.ones((num_examples, nnz), np.float32)

    true_w0 = rng.normal() * 0.1
    true_w = rng.normal(size=(num_features,)) * 0.3
    true_v = rng.normal(size=(num_features, rank)) * 0.4

    rows = true_v[ids]
    s = rows.sum(axis=1)
    interaction = 0.5 * ((s * s).sum(-1) - (rows * rows).sum((1, 2)))
    score = true_w0 + true_w[ids].sum(1) + interaction
    score = (score - score.mean()) / (score.std() + 1e-9) * scale
    labels = (rng.random(num_examples) < 1.0 / (1.0 + np.exp(-score))).astype(
        np.float32)
    return ids, vals, labels


def field_local(ids: np.ndarray, bucket: int) -> np.ndarray:
    """Global per-field-offset ids -> ids in ``[0, bucket)`` per field."""
    return ids - (np.arange(ids.shape[1], dtype=ids.dtype) * bucket)[None, :]


def zipf_pool(rows: int, fields: int, bucket: int, seed: int):
    """``(ids int32 [rows, fields], vals float32 [rows, fields])`` of
    field-local Zipf(1.5) ids and one-hot values: the rows every scoring
    request of a run is a slice of, so hot ids recur across requests as
    they do across one user's candidates."""
    rng = np.random.default_rng((seed, 0x5C0))
    ids = (rng.zipf(ZIPF_A, size=(rows, fields)) % bucket).astype(np.int32)
    return ids, np.ones((rows, fields), np.float32)


def checksum(*arrays) -> str:
    """sha256 over dtype, shape and bytes of each array, in order."""
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()
