"""Tier-1 interpret-mode smoke for EVERY Pallas kernel (ISSUE 8).

``ops.pallas_fused.interpret_smokes()`` is the registry: one tiny
interpret-mode invocation per shipped kernel. The smoke asserts each
runs finite, and pins the registry against the ``ops/pallas_*`` module
surface so a new kernel cannot ship unregistered (and therefore
unsmoked). Skips cleanly when Pallas interpret mode is unavailable on
the installed jax.
"""

import ast
import os

import numpy as np
import pytest

pallas = pytest.importorskip(
    "jax.experimental.pallas",
    reason="Pallas (and its interpret mode) unavailable on this jax")

from fm_spark_tpu.ops import pallas_fused  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS = os.path.join(REPO, "fm_spark_tpu", "ops")


def _smokes():
    try:
        return pallas_fused.interpret_smokes()
    except Exception as e:  # pragma: no cover - env-specific
        pytest.skip(f"Pallas interpret smokes unavailable: {e!r}")


def test_registry_names_every_kernel_module():
    """Every ops/pallas_*.py module must contribute at least one smoke
    (a module with zero registered kernels is dead or unsmoked)."""
    smokes = _smokes()
    modules = {name.split(".")[0] for name in smokes}
    on_disk = {f[:-3] for f in os.listdir(OPS)
               if f.startswith("pallas_") and f.endswith(".py")}
    assert on_disk == modules, (
        f"kernel modules {on_disk - modules} have no interpret smoke "
        f"registered in pallas_fused.interpret_smokes()")


def test_registry_covers_every_public_pallas_call():
    """Pin the registry against the modules' public API: every top-level
    public function that invokes pl.pallas_call (directly or via its
    module-private helper) must be registered. AST-derived so a new
    kernel entry point turns this red until it registers."""
    smokes = _smokes()
    registered = {name.split(".", 1)[1] for name in smokes}
    public_kernels = set()
    for fname in sorted(os.listdir(OPS)):
        if not (fname.startswith("pallas_") and fname.endswith(".py")):
            continue
        with open(os.path.join(OPS, fname)) as f:
            tree = ast.parse(f.read())
        # Functions that directly contain a pallas_call.
        callers = set()
        for node in tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and sub.func.attr == "pallas_call"):
                    callers.add(node.name)
        # Public functions that are direct callers, or call a PRIVATE
        # direct caller (one hop — the _fwd_field pattern).
        private_callers = {c for c in callers if c.startswith("_")}
        for node in tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            if node.name.startswith("_"):
                continue
            names = {sub.func.id for sub in ast.walk(node)
                     if isinstance(sub, ast.Call)
                     and isinstance(sub.func, ast.Name)}
            if node.name in callers or names & private_callers:
                public_kernels.add(node.name)
    missing = public_kernels - registered
    assert not missing, (
        f"public Pallas kernels {missing} are not registered in "
        "pallas_fused.interpret_smokes()")


@pytest.mark.parametrize("name", sorted(pallas_fused.interpret_smokes()))
def test_kernel_interpret_smoke(name):
    import jax

    out = pallas_fused.interpret_smokes()[name]()
    for leaf in jax.tree_util.tree_leaves(out):
        arr = np.asarray(leaf)
        assert arr.size > 0, name
        assert np.isfinite(arr).all(), f"{name} produced non-finite output"
