"""faultcat — faultpoint catalog consistency.

Every instrumented faultpoint site (``self._fault("x")``,
``fs.fire("x")``, ``fs.should("x")``, ``self._fault_point("x")``,
``self._faulted("x")`` — tiering.py's "did it fire" wrapper) must
name a point in ``faults.FAULT_POINTS``, and every cataloged point
must still have at least one site — so the chaos matrix can never arm
a point that silently tests nothing, and a removed call site can't
leave a ghost entry behind.  (RESILIENCE.md's operator-facing table is
checked against the same catalog by tools/check_metrics.py.)
"""
from __future__ import annotations

import ast
from typing import Dict, List, Tuple

from . import Violation
from .engine import LintContext

PASS_ID = "faultcat"

_SITE_FUNCS = {"fire", "should", "_fault", "_fault_point",
               "_fault_tick", "_faulted"}


def _catalog(ctx: LintContext):
    for sf in ctx.core_files():
        if not sf.rel.endswith("faults.py"):
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "FAULT_POINTS"
                    for t in node.targets):
                if isinstance(node.value, ast.Dict):
                    return sf, {
                        k.value: k.lineno for k in node.value.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)}
    return None, None


def run(ctx: LintContext) -> List[Violation]:
    out: List[Violation] = []
    cat_sf, catalog = _catalog(ctx)
    if catalog is None:
        return out  # fixture trees without faults.py
    sites: Dict[str, Tuple[str, int]] = {}
    for sf in ctx.core_files():
        if sf.rel.endswith("faults.py"):
            continue  # the implementation's own generic fire(name)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else "")
            if name not in _SITE_FUNCS or not node.args:
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                continue
            point = arg.value
            sites.setdefault(point, (sf.rel, node.lineno))
            if point not in catalog:
                out.append(Violation(
                    sf.rel, node.lineno, PASS_ID,
                    f"faultpoint {point!r} fired here but missing from "
                    f"faults.FAULT_POINTS — add it to the catalog (and "
                    f"RESILIENCE.md)"))
    for point, line in catalog.items():
        if point not in sites:
            out.append(Violation(
                cat_sf.rel, line, PASS_ID,
                f"FAULT_POINTS catalogs {point!r} but no instrumented "
                f"site fires it — the chaos matrix would arm a no-op"))
    return out
