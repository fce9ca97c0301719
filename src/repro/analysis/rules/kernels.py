"""kernel-triangle: every ``pallas_call`` entry point in ``kernels/``
needs (1) a named numpy/jnp oracle in ``kernels/ref.py`` and (2) a
parity test pinning kernel == oracle.

The roofline work only trusts a kernel when the triangle closes:
kernel ↔ oracle ↔ test.  A kernel without an oracle cannot be
parity-checked; an oracle without a test silently drifts.  The mapping
is explicit (names are not mechanically derivable: ``flash_attention``
parity-checks against ``ref.attention``; the fused flat ops are
exercised through wrappers in three different test files), so adding a
kernel means adding a ``TRIANGLE`` entry — an unmapped ``pallas_call``
site is itself a violation, as is a stale entry whose kernel is gone.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set

from repro.analysis.framework import (FileContext, Rule, Violation,
                                      call_name, register)

# kernel entry -> its module (stale detection), oracles that must be
# defined in kernels/ref.py, and parity-test symbols that must exist
# under tests/.
TRIANGLE: Dict[str, dict] = {
    "flash_attention": {
        "module": "flash_attention", "oracles": ["attention"],
        "tests": ["test_flash_attention"]},
    "mamba_scan": {
        "module": "mamba_scan", "oracles": ["mamba_scan"],
        "tests": ["test_mamba_scan"]},
    "wkv6": {
        "module": "rwkv6_scan", "oracles": ["wkv6"],
        "tests": ["test_wkv6"]},
    "quantize_int8": {
        "module": "quantize", "oracles": ["quantize_int8"],
        "tests": ["test_quantize_roundtrip"]},
    "dequantize_int8": {
        "module": "quantize", "oracles": ["dequantize_int8"],
        "tests": ["test_quantize_roundtrip"]},
    "threshold_sparsify": {
        "module": "topk_mask", "oracles": ["threshold_sparsify"],
        "tests": ["test_threshold_sparsify"]},
    "blocked_topk_stats": {
        "module": "topk_mask", "oracles": ["blocked_topk_stats"],
        "tests": ["test_blocked_sparsify_kept_plus_residual_bit_exact"]},
    "threshold_sparsify_exact": {
        "module": "topk_mask", "oracles": ["threshold_sparsify_exact"],
        "tests": ["test_select_topk_deterministic_k_under_ties"]},
    "_blocked_call": {
        "module": "vc_asgd_update",
        "oracles": ["vc_asgd_lerp", "vc_asgd_dc_lerp"],
        "tests": ["test_fused_lerp", "test_fused_dc_lerp"]},
    "assimilate_flat": {
        "module": "vc_asgd_update", "oracles": ["vc_asgd_lerp"],
        "tests": ["test_assimilate_flat_matches_per_leaf_oracle",
                  "test_assimilate_flat_kernel_close"]},
    "adam_update_flat": {
        "module": "vc_asgd_update", "oracles": ["adam_update"],
        "tests": ["test_fused_adam_flat"]},
    "easgd_elastic_flat": {
        "module": "vc_asgd_update", "oracles": ["easgd_elastic"],
        "tests": ["test_fused_easgd_flat"]},
}


def _pallas_entries(tree: ast.AST) -> List[ast.FunctionDef]:
    out = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for call in ast.walk(fn):
            if isinstance(call, ast.Call) \
                    and call_name(call).rsplit(".", 1)[-1] == "pallas_call":
                out.append(fn)
                break
    return out


class _TestIndex:
    """Lazy, per-repo-root concatenation of tests/test_*.py sources."""

    def __init__(self):
        self._cache: Dict[Path, str] = {}

    def source(self, repo_root: Path) -> str:
        if repo_root not in self._cache:
            chunks = []
            tdir = repo_root / "tests"
            if tdir.is_dir():
                for f in sorted(tdir.glob("test_*.py")):
                    try:
                        chunks.append(f.read_text())
                    except OSError:
                        pass
            self._cache[repo_root] = "\n".join(chunks)
        return self._cache[repo_root]


@register
class KernelTriangleRule(Rule):
    name = "kernel-triangle"
    doc = ("every pallas_call entry in kernels/ needs an oracle in "
           "kernels/ref.py and a parity test under tests/ (TRIANGLE map)")

    def __init__(self):
        self._tests = _TestIndex()

    def wants(self, ctx: FileContext) -> bool:
        return (ctx.under("kernels") and not ctx.endswith("kernels/ref.py")
                and "pallas_call" in ctx.source)

    def check(self, ctx: FileContext) -> Iterable[Violation]:
        out: List[Violation] = []
        ref_path = ctx.path.parent / "ref.py"
        ref_src = ref_path.read_text() if ref_path.is_file() else None
        entries = _pallas_entries(ctx.tree)
        defined = {fn.name for fn in ast.walk(ctx.tree)
                   if isinstance(fn, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))}
        for fn in entries:
            tri = TRIANGLE.get(fn.name)
            if tri is None:
                out.append(ctx.violation(
                    "kernel-triangle", fn,
                    f"pallas_call entry `{fn.name}` has no TRIANGLE "
                    f"entry — add its oracle + parity test and register "
                    f"them in analysis/rules/kernels.py"))
                continue
            if ref_src is None:
                out.append(ctx.violation(
                    "kernel-triangle", fn,
                    f"`{fn.name}` needs oracle(s) "
                    f"{tri['oracles']} but kernels/ref.py is missing"))
            else:
                for oracle in tri["oracles"]:
                    if f"def {oracle}(" not in ref_src:
                        out.append(ctx.violation(
                            "kernel-triangle", fn,
                            f"oracle `{oracle}` for kernel `{fn.name}` "
                            f"not defined in kernels/ref.py"))
            tsrc = self._tests.source(ctx.repo_root)
            for test in tri["tests"]:
                if f"def {test}(" not in tsrc:
                    out.append(ctx.violation(
                        "kernel-triangle", fn,
                        f"parity test `{test}` for kernel `{fn.name}` "
                        f"not found under tests/"))
        # stale map entries for THIS module
        mod = Path(ctx.relpath).stem
        for name, tri in sorted(TRIANGLE.items()):
            if tri["module"] == mod and name not in defined:
                out.append(ctx.violation(
                    "kernel-triangle", 1,
                    f"TRIANGLE maps `{name}` to module `{mod}` but no "
                    f"such function exists — remove the stale entry"))
        return out
