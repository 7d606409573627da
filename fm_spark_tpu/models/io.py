"""Final-model save/load — parity with ``FMModel.save/load``.

The reference durably saves only the final model (weights + metadata;
SURVEY.md §3.4-§3.5 — mid-training fault tolerance is Spark lineage, and
the rebuild's richer story lives in :mod:`fm_spark_tpu.checkpoint`). Format
here: a directory with ``spec.json`` (model family + hyperparams + the
index of parameter files) and ``params/*.npy``, each leaf flattened and
cut into files of at most :data:`MAX_FILE_BYTES` — config 3's 2.7 GB of
tables in one ``params.npz`` was refused ("File too large") on a chip
machine that limits file size. The format is self-describing so a model
can be reloaded without knowing its family in advance.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import numpy as np


# No file save_model writes is larger than this.
MAX_FILE_BYTES = 16 << 20
_NPY_HEADER_ROOM = 4096

_FAMILIES = {}


def _family_name(spec) -> str:
    return type(spec).__name__


def _register_families():
    # Deferred import to avoid a cycle models.io <-> models.__init__.
    from fm_spark_tpu.models.fm import FMSpec
    from fm_spark_tpu.models.ffm import FFMSpec
    from fm_spark_tpu.models.deepfm import DeepFMSpec
    from fm_spark_tpu.models.field_dcn import FieldDCNSpec
    from fm_spark_tpu.models.field_deepfm import FieldDeepFMSpec
    from fm_spark_tpu.models.field_dlrm import FieldDLRMSpec
    from fm_spark_tpu.models.field_fm import FieldFMSpec
    from fm_spark_tpu.models.field_ffm import FieldFFMSpec
    from fm_spark_tpu.models.field_xdeepfm import FieldXDeepFMSpec

    _FAMILIES.update(
        FMSpec=FMSpec,
        FFMSpec=FFMSpec,
        DeepFMSpec=DeepFMSpec,
        FieldDCNSpec=FieldDCNSpec,
        FieldDeepFMSpec=FieldDeepFMSpec,
        FieldDLRMSpec=FieldDLRMSpec,
        FieldFMSpec=FieldFMSpec,
        FieldFFMSpec=FieldFFMSpec,
        FieldXDeepFMSpec=FieldXDeepFMSpec,
    )


def _leaf_name(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keypath)


def save_model(path: str, spec, params: dict) -> None:
    """Write spec.json + params/*.npy under ``path`` (a directory)."""
    os.makedirs(os.path.join(path, "params"), exist_ok=True)
    meta = {"family": _family_name(spec), "spec": dataclasses.asdict(spec)}
    # JSON can't hold inf; the regression clip defaults are ±inf.
    for key in ("min_target", "max_target"):
        if key in meta["spec"] and not np.isfinite(meta["spec"][key]):
            meta["spec"][key] = None
    dtypes, shapes, files = {}, {}, {}
    leaves = jax.tree_util.tree_leaves_with_path(params)
    for i, (keypath, leaf) in enumerate(leaves):
        name = _leaf_name(keypath)
        arr = np.asarray(leaf)
        dtypes[name] = str(arr.dtype) if arr.dtype.kind != "V" else str(leaf.dtype)
        if arr.dtype.kind == "V":
            # npy can't store ml_dtypes (bfloat16 → raw '|V2', unloadable);
            # widen to float32 for storage and restore the dtype on load.
            arr = np.asarray(jax.numpy.asarray(leaf).astype(jax.numpy.float32))
        shapes[name] = list(arr.shape)
        flat = arr.reshape(-1)
        step = (MAX_FILE_BYTES - _NPY_HEADER_ROOM) // arr.dtype.itemsize
        files[name] = []
        for j, lo in enumerate(range(0, max(flat.size, 1), step)):
            fname = f"{i:05d}.{j:04d}.npy"
            np.save(os.path.join(path, "params", fname), flat[lo:lo + step])
            files[name].append(fname)
    meta.update(param_dtypes=dtypes, param_shapes=shapes, param_files=files)
    # Last: an index on disk names only files that are complete.
    with open(os.path.join(path, "spec.json"), "w") as f:
        json.dump(meta, f, indent=2)


def _read_meta(path: str) -> dict:
    with open(os.path.join(path, "spec.json")) as f:
        return json.load(f)


def _read_array(path: str, meta: dict, name: str) -> np.ndarray:
    parts = [np.load(os.path.join(path, "params", fname))
             for fname in meta["param_files"][name]]
    flat = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return flat.reshape(meta["param_shapes"][name])


def load_array(path: str, name: str) -> np.ndarray:
    """One saved leaf (``"vw/3"``, ``"w0"``) as NumPy, in its storage
    dtype, without loading the rest of the model."""
    return _read_array(path, _read_meta(path), name)


def load_model(path: str):
    """Read back ``(spec, params)`` written by :func:`save_model`."""
    _register_families()
    meta = _read_meta(path)
    spec_kwargs = dict(meta["spec"])
    import math

    if spec_kwargs.get("min_target") is None:
        spec_kwargs["min_target"] = -math.inf
    if spec_kwargs.get("max_target") is None:
        spec_kwargs["max_target"] = math.inf
    for key in ("mlp_dims", "bottom_mlp_dims", "hots", "cin_layers"):
        if key in spec_kwargs:
            spec_kwargs[key] = tuple(spec_kwargs[key])
    # Every FieldFM model saved before the field went carries
    # ``"table_layout": "row"``, the storage every model has now.
    if spec_kwargs.pop("table_layout", "row") != "row":
        raise ValueError(
            f"{path}/spec.json has \"table_layout\": "
            f"{meta['spec']['table_layout']!r}: transposed table storage "
            "was removed; only 'row' models load"
        )
    spec = _FAMILIES[meta["family"]](**spec_kwargs)
    # Rebuild the nested pytree from an example structure.
    example = jax.eval_shape(spec.init, jax.random.key(0))
    leaves_with_path = jax.tree_util.tree_leaves_with_path(example)
    treedef = jax.tree_util.tree_structure(example)
    dtypes = meta.get("param_dtypes", {})
    ordered = []
    for keypath, _ in leaves_with_path:
        name = _leaf_name(keypath)
        arr = jax.numpy.asarray(_read_array(path, meta, name))
        want = dtypes.get(name)
        if want and str(arr.dtype) != want:
            arr = arr.astype(want)
        ordered.append(arr)
    params = jax.tree_util.tree_unflatten(treedef, ordered)
    return spec, params
