"""Reproducible build for the native kernels: fasthash.cpp → libfmfast-<hash>.so.

The recipe itself (compiler, flags, the content-hashed output name)
lives in ``fm_spark_tpu/native/__init__.py``, which builds lazily on
first use; this script runs the same build by hand and is the drift
detector for the exported surface:

    python tools/build_native.py            # build (if absent) + list
    python tools/build_native.py --check    # build to a temp dir and
                                            # diff exported fm_* symbols
                                            # against EXPECTED_SYMBOLS
    python tools/build_native.py --print-symbols

``--check`` exits nonzero when the source exports a symbol set that
differs from :data:`EXPECTED_SYMBOLS` (someone added an entry point
without registering it here, or dropped one the ctypes bindings name —
the loader would then raise at first use). Tier-1 wiring:
tests/test_native_stream.py runs ``--check`` and skips cleanly when no
compiler is present.
"""

import argparse
import ctypes
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from fm_spark_tpu import native  # noqa: E402

#: The extern "C" surface the ctypes bindings bind. Adding an entry
#: point to fasthash.cpp without listing it here fails --check.
EXPECTED_SYMBOLS = (
    "fm_murmur3_32",
    "fm_hash_bytes_batch",
    "fm_hash_u64_batch",
    "fm_parse_criteo",
    "fm_parse_criteo_rows",
    "fm_parse_avazu_rows",
    "fm_parse_libsvm_rows",
    "fm_dedup_aux",
    "fm_compact_aux",
    "fm_gather_rows",
)


def exported_symbols(so_path: str) -> list[str]:
    """fm_* symbols exported by a shared library. Prefers ``nm -D``
    (sees everything); falls back to ctypes lookups against
    EXPECTED_SYMBOLS when binutils is absent (extra symbols then go
    undetected, missing ones do not)."""
    nm = shutil.which("nm")
    if nm is not None:
        proc = subprocess.run([nm, "-D", "--defined-only", so_path],
                              capture_output=True, text=True)
        if proc.returncode == 0:
            return sorted(
                line.split()[-1] for line in proc.stdout.splitlines()
                if line.split() and line.split()[-1].startswith("fm_")
            )
    lib = ctypes.CDLL(so_path)
    return sorted(s for s in EXPECTED_SYMBOLS if hasattr(lib, s))


def check() -> int:
    """Build fresh and diff its symbols against EXPECTED_SYMBOLS."""
    rc = 0
    with tempfile.TemporaryDirectory(prefix="fm_build_native_") as tmp:
        fresh = os.path.join(tmp, "libfmfast.so")
        native.build(fresh)
        got = set(exported_symbols(fresh))
        want = set(EXPECTED_SYMBOLS)
        if got != want:
            rc = 1
            for sym in sorted(want - got):
                print(f"MISSING from fresh build: {sym}", file=sys.stderr)
            for sym in sorted(got - want):
                print(f"UNREGISTERED export: {sym} (add it to "
                      "EXPECTED_SYMBOLS)", file=sys.stderr)
    if rc == 0:
        print(f"symbol check OK: {len(want)} exported fm_* symbols")
    return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="build to a temp dir and diff exported symbols "
                         "against EXPECTED_SYMBOLS")
    ap.add_argument("--print-symbols", action="store_true",
                    dest="print_symbols",
                    help="list the built library's fm_* exports")
    args = ap.parse_args()
    so = native.lib_path()
    if args.print_symbols:
        if not os.path.exists(so):
            print(f"error: {so} does not exist (run tools/build_native.py "
                  "first)", file=sys.stderr)
            return 2
        for sym in exported_symbols(so):
            print(sym)
        return 0
    if shutil.which(native.COMPILER) is None:
        print(f"error: {native.COMPILER} not found on PATH",
              file=sys.stderr)
        return 2
    if args.check:
        return check()
    if not os.path.exists(so):
        native.build(so)
    print(f"built {so}")
    for sym in exported_symbols(so):
        print(f"  {sym}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
