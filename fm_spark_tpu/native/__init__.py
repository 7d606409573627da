"""ctypes bindings for the native preprocessing kernels (fasthash.cpp).

Compiles the shared library on first use (g++ is in the image; pybind11 is
not, so the binding layer is plain ctypes over flat numpy buffers). Every
function has a pure-numpy fallback in :mod:`fm_spark_tpu.data.hashing`
with bit-identical output; ``available()`` says which path you're on, and
nothing in the package *requires* the native path — it is a throughput
lever for the host side of the input pipeline (text→packed preprocessing,
the packed row gather, the compact aux), not a correctness dependency.

The library is built to ``libfmfast-<hash>.so``, the hash taken over
``fasthash.cpp`` and the compile line, and only that file is ever loaded:
a binary left behind by an older source (the tree is copied as it stands
onto other machines) has another name and is ignored. What remains to
fall back on is a machine with no compiler; a library that loads but
lacks a symbol the bindings name is a bug and raises.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fasthash.cpp")
#: The pinned compile line (tools/build_native.py builds with the same).
COMPILER = "g++"
FLAGS = ("-O3", "-shared", "-fPIC", "-std=c++17")

_lock = threading.Lock()
_lib = None
_build_error: str | None = None


def lib_path() -> str:
    """Where the library for THIS source lives: the name carries the
    content hash of ``fasthash.cpp`` + the compile line."""
    h = hashlib.sha256(" ".join((COMPILER, *FLAGS)).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"libfmfast-{h.hexdigest()[:16]}.so")


def build(out_path: str) -> None:
    """Compile ``fasthash.cpp`` to ``out_path`` (raises on failure).
    Written under a temporary name and renamed, so a concurrent process
    never loads a half-written library."""
    tmp = f"{out_path}.{os.getpid()}.tmp"
    cmd = [COMPILER, *FLAGS, _SRC, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300)
        if proc.returncode != 0:
            raise RuntimeError(
                f"{' '.join(cmd)} failed (rc={proc.returncode}):\n"
                f"{proc.stderr[-2000:]}")
        os.replace(tmp, out_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        path = lib_path()
        if not os.path.exists(path):
            try:
                build(path)
            except (OSError, RuntimeError,
                    subprocess.TimeoutExpired) as e:
                # g++ missing, read-only dir, compile error, ...
                _build_error = f"{type(e).__name__}: {e}"
                return None
        lib = ctypes.CDLL(path)
        lib.fm_murmur3_32.restype = ctypes.c_uint32
        lib.fm_murmur3_32.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32,
        ]
        lib.fm_hash_bytes_batch.restype = None
        lib.fm_hash_bytes_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int, ctypes.c_void_p,
        ]
        lib.fm_hash_u64_batch.restype = None
        lib.fm_hash_u64_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int32, ctypes.c_int, ctypes.c_void_p,
        ]
        lib.fm_parse_criteo.restype = ctypes.c_int64
        lib.fm_parse_criteo.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.fm_dedup_aux.restype = None
        lib.fm_dedup_aux.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.fm_compact_aux.restype = ctypes.c_int32
        lib.fm_compact_aux.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p,
        ]
        lib.fm_gather_rows.restype = None
        lib.fm_gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_int32, ctypes.c_int, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        for sym in ("fm_parse_criteo_rows", "fm_parse_avazu_rows"):
            fn = getattr(lib, sym)
            fn.restype = ctypes.c_int64
            fn.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32,
                ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p,
            ]
        lib.fm_parse_libsvm_rows.restype = ctypes.c_int64
        lib.fm_parse_libsvm_rows.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    """True if the native library compiled and loaded on this machine."""
    return _load() is not None


def build_error() -> str | None:
    _load()
    return _build_error


def murmur3_32(data: bytes, seed: int = 0) -> int:
    lib = _load()
    if lib is None:
        from fm_spark_tpu.data import hashing

        return hashing.murmur3_32(data, seed)
    return int(lib.fm_murmur3_32(data, len(data), seed))


def hash_tokens_batch(tokens: list[bytes], fields: np.ndarray, bucket: int,
                      per_field: bool = True) -> np.ndarray:
    """Native batch token hashing; falls back to the numpy reference."""
    lib = _load()
    if lib is None:
        from fm_spark_tpu.data import hashing

        return hashing.hash_tokens_batch(tokens, fields, bucket, per_field)
    buf = b"".join(tokens)
    offsets = np.zeros(len(tokens) + 1, np.int64)
    np.cumsum([len(t) for t in tokens], out=offsets[1:])
    fields32 = np.ascontiguousarray(fields, np.int32)
    out = np.empty(len(tokens), np.int64)
    lib.fm_hash_bytes_batch(
        buf, offsets.ctypes.data, len(tokens), fields32.ctypes.data,
        bucket, int(per_field), out.ctypes.data,
    )
    return out


def hash_u64_batch(keys: np.ndarray, fields: np.ndarray, bucket: int,
                   per_field: bool = True) -> np.ndarray:
    lib = _load()
    keys = np.ascontiguousarray(keys, np.uint64)
    fields32 = np.ascontiguousarray(fields, np.int32)
    if lib is None:
        from fm_spark_tpu.data import hashing

        h = hashing.murmur3_u64(keys, fields32.astype(np.uint32)) % np.uint32(bucket)
        out = h.astype(np.int64)
        if per_field:
            out += fields32.astype(np.int64) * bucket
        return out
    out = np.empty(keys.shape[0], np.int64)
    lib.fm_hash_u64_batch(
        keys.ctypes.data, keys.shape[0], fields32.ctypes.data, bucket,
        int(per_field), out.ctypes.data,
    )
    return out


CRITEO_FIELDS = 39


def parse_criteo_chunk(chunk: bytes, bucket: int, per_field: bool = True,
                       max_rows: int | None = None):
    """Parse a chunk of Criteo TSV → (ids[N,39] int32, labels[N] int8,
    consumed_bytes). Only complete lines are consumed; feed the remainder
    back with the next chunk. Requires the native library (the Python
    fallback lives in data/criteo.py)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    if max_rows is None:
        max_rows = chunk.count(b"\n")
    ids = np.empty((max_rows, CRITEO_FIELDS), np.int32)
    labels = np.empty(max_rows, np.int8)
    consumed = ctypes.c_int64(0)
    bad_pos = ctypes.c_int64(-1)
    n = lib.fm_parse_criteo(
        chunk, len(chunk), bucket, int(per_field), max_rows,
        ids.ctypes.data, labels.ctypes.data, ctypes.byref(consumed),
        ctypes.byref(bad_pos),
    )
    if bad_pos.value >= 0:
        lineno = chunk[: bad_pos.value].count(b"\n") + 1
        snippet = chunk[bad_pos.value: bad_pos.value + 60]
        raise ValueError(
            f"malformed criteo line (chunk line {lineno}): {snippet!r}"
        )
    return ids[:n], labels[:n], int(consumed.value)


# Cap on the counting sort's O(bucket) scratch (int64 entries),
# AGGREGATE across the min(F, hw) worker threads that each hold one
# O(bucket) vector: 1 << 27 ≈ 1GB total — beyond that the numpy argsort
# fallback is the safer trade. (Dividing the cap by the thread count is
# what keeps F parallel workers from multiplying a "reasonable"
# per-thread scratch into tens of host GB.)
_COUNTING_SORT_MAX_BUCKET = 1 << 27


def _counting_sort_fits(bucket: int, f: int) -> bool:
    n_threads = max(1, min(f, os.cpu_count() or 1))
    return bucket * n_threads <= _COUNTING_SORT_MAX_BUCKET


def dedup_aux_native(ids: np.ndarray, bucket: int):
    """Native counting-sort dedup precompute (fm_dedup_aux); returns
    ``(order, seg, useg, ord_first)`` int32 ``[F, B]`` arrays, or None
    when the library is unavailable (caller falls back to numpy —
    ops/scatter.dedup_aux) or the bucket count would make the aggregate
    O(bucket)-per-worker scratch unreasonable."""
    lib = _load()
    ids = np.asarray(ids)
    b, f = ids.shape
    if lib is None or not _counting_sort_fits(bucket, f):
        return None
    ids = np.ascontiguousarray(ids, np.int32)
    out = tuple(np.empty((f, b), np.int32) for _ in range(4))
    lib.fm_dedup_aux(
        ids.ctypes.data, b, f, int(bucket),
        out[0].ctypes.data, out[1].ctypes.data, out[2].ctypes.data,
        out[3].ctypes.data,
    )
    return out


def compact_aux_native(ids: np.ndarray, cap: int):
    """Native counting-sort COMPACT aux (fm_compact_aux); returns
    ``(useg, segstart, segend, order, inv)`` per
    ops/scatter.compact_aux's contract, or None when the library is
    unavailable. Raises ValueError on per-field unique-count overflow,
    matching the numpy path."""
    lib = _load()
    if lib is None:
        return None
    ids = np.ascontiguousarray(ids, np.int32)
    b, f = ids.shape
    bucket = int(ids.max()) + 1 if b else 1
    if not _counting_sort_fits(bucket, f):
        # The C++ counting sort allocates an O(bucket) scratch vector
        # PER WORKER THREAD (min(F, hw) workers); one stray giant id
        # would turn that into multi-GB allocations inside the prefetch
        # producer. Fall back to the numpy argsort path, O(B) memory.
        return None
    useg = np.empty((f, cap), np.int32)
    segstart = np.empty((f, cap), np.int32)
    segend = np.empty((f, cap), np.int32)
    order = np.empty((f, b), np.int32)
    inv = np.empty((f, b), np.int32)
    overflow = lib.fm_compact_aux(
        ids.ctypes.data, b, f, bucket, int(cap),
        useg.ctypes.data, segstart.ctypes.data, segend.ctypes.data,
        order.ctypes.data, inv.ctypes.data,
    )
    if overflow >= 0:
        from fm_spark_tpu.ops.scatter import CompactCapOverflow

        raise CompactCapOverflow(
            f"field {overflow}: unique ids > compact cap {cap}; raise "
            "compact_cap (it must bound the per-field per-batch "
            "unique-id count)"
        )
    return useg, segstart, segend, order, inv


def gather_rows_native(ids: np.ndarray, vals: np.ndarray | None,
                       labels: np.ndarray, sel: np.ndarray,
                       bucket: int = 0, n_threads: int = 0):
    """Fused packed-batch assembly (fm_gather_rows): gather ``sel`` rows
    out of the [N, F] int32 id table (and f32 vals table when present),
    converting to field-local ids in the same pass when ``bucket > 0``
    and casting int8 labels to f32. Returns ``(ids, vals, labels)`` with
    ``vals = None`` when the source stores none (caller supplies its
    cached all-ones array), or None when the native library is
    unavailable.

    Bit-identical to the numpy fallback in
    :meth:`fm_spark_tpu.data.packed.PackedDataset.assemble` (int32
    subtraction and int8->f32 cast are exact in both)."""
    lib = _load()
    if lib is None:
        return None
    if ids.dtype != np.int32 or labels.dtype != np.int8:
        return None  # non-standard packed arrays: let numpy handle it
    if vals is not None and vals.dtype != np.float32:
        return None
    if not (ids.flags.c_contiguous and labels.flags.c_contiguous
            and (vals is None or vals.flags.c_contiguous)):
        return None  # packed memmaps are contiguous; anything else -> numpy
    sel = np.ascontiguousarray(sel, np.int64)
    b = sel.shape[0]
    f = ids.shape[1]
    if b and (int(sel.min()) < 0 or int(sel.max()) >= ids.shape[0]):
        # The C kernel does no bounds checks; numpy's fancy indexing
        # semantics (IndexError / negative wraparound) must win instead
        # of a silent out-of-bounds read.
        return None
    out_ids = np.empty((b, f), np.int32)
    out_vals = np.empty((b, f), np.float32) if vals is not None else None
    out_labels = np.empty((b,), np.float32)
    lib.fm_gather_rows(
        ids.ctypes.data,
        (vals.ctypes.data if vals is not None else None),
        labels.ctypes.data, sel.ctypes.data, b, f, int(bucket),
        int(n_threads),
        out_ids.ctypes.data,
        (out_vals.ctypes.data if out_vals is not None else None),
        out_labels.ctypes.data,
    )
    return out_ids, out_vals, out_labels


# -------------------------------------------------- streaming chunk parse

#: Per-row status codes shared with the C++ chunk-row parsers: OK rows
#: are guaranteed bit-identical to the pure-Python parser AND pre-
#: validated against the RecordGuard value contract; SKIP rows carry no
#: record (blank / libsvm comment); REPARSE rows route back through the
#: per-line Python oracle so every verdict and error string stays exact.
STREAM_OK, STREAM_SKIP, STREAM_REPARSE = 0, 1, 2

_STREAM_SYMBOLS = {
    "criteo": "fm_parse_criteo_rows",
    "avazu": "fm_parse_avazu_rows",
    "libsvm": "fm_parse_libsvm_rows",
}

#: Hashed fields per fixed-field dataset (mirrors data/criteo.py and
#: data/avazu.py NUM_FIELDS without importing them — the data layer
#: imports this module).
STREAM_FIELDS = {"criteo": 39, "avazu": 23}


def stream_parse_available(dataset: str) -> bool:
    """True iff the library loaded and has a chunk-row parser for
    ``dataset``."""
    return dataset in _STREAM_SYMBOLS and _load() is not None


def parse_stream_chunk(dataset: str, chunk: bytes, *, bucket: int = 0,
                       per_field: bool = True, num_features: int = 0,
                       max_nnz: int = 0, zero_based: bool = False):
    """Chunk-row parse for the streaming ingest (data/native_stream.py).

    ``chunk`` must end on a line boundary (terminating ``\\n``). Returns
    ``(ids, vals, labels, status, rowlen)`` where ``ids`` is
    ``[n_lines, F]`` int32 (``F = max_nnz`` for libsvm, the dataset's
    field count otherwise), ``vals`` is ``[n_lines, max_nnz]`` float32
    for libsvm and ``None`` for the all-ones criteo/avazu formats,
    ``labels`` float32, ``status`` uint8 per :data:`STREAM_OK` /
    :data:`STREAM_SKIP` / :data:`STREAM_REPARSE`, and ``rowlen`` int64
    per-row consumed bytes (newline included) — the exactly-once
    cursor's advance array. Returns ``None`` when the native parser is
    unavailable or the id space overflows int32 (callers fall back to
    the pure-Python path).
    """
    lib = _load()
    sym = _STREAM_SYMBOLS.get(dataset)
    if lib is None or sym is None:
        return None
    n = chunk.count(b"\n")
    status = np.empty(n, np.uint8)
    rowlen = np.empty(n, np.int64)
    labels = np.empty(n, np.float32)
    if dataset == "libsvm":
        S = int(max_nnz)
        if S < 1:
            return None
        ids = np.empty((n, S), np.int32)
        vals = np.empty((n, S), np.float32)
        got = lib.fm_parse_libsvm_rows(
            chunk, len(chunk), int(zero_based), S, int(num_features), n,
            ids.ctypes.data, vals.ctypes.data, labels.ctypes.data,
            status.ctypes.data, rowlen.ctypes.data,
        )
    else:
        F = STREAM_FIELDS[dataset]
        if per_field and F * int(bucket) > np.iinfo(np.int32).max:
            return None  # id space overflows int32 — let Python decide
        ids = np.empty((n, F), np.int32)
        vals = None
        got = getattr(lib, sym)(
            chunk, len(chunk), int(bucket), int(per_field),
            int(num_features), n, ids.ctypes.data, labels.ctypes.data,
            status.ctypes.data, rowlen.ctypes.data,
        )
    if got != n:
        raise RuntimeError(
            f"native {dataset} chunk parse scanned {got} of {n} lines — "
            "the chunk did not end on a line boundary"
        )
    return ids, vals, labels, status, rowlen
