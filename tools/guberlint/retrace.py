"""retrace — jit call sites must be retrace-stable.

``jax.jit`` caches compiled programs by (shapes, dtypes, weak-type
flags, static-arg hashes).  A call site that drifts any of those
recompiles SILENTLY — a minutes-long cold TPU compile in the middle of
steady-state serving, surfacing only as a caller timeout (the exact
failure the dispatcher's stall watchdog was built for).  This pass
pins the two statically-checkable drift classes at every call site of
a jit-bound callable (``f = jax.jit(...)`` at module scope, or
``self._f = jax.jit(...)``):

- **dtype drift**: one positional slot fed Python-scalar ints at one
  site and floats (or a different ``np.<dtype>`` wrap) at another —
  each flavor compiles its own program, and alternating callers
  recompile per wave.  Weak-typed Python scalars are classified
  (``py-int`` / ``py-float`` / ``py-bool``) and only flagged when the
  slot actually sees more than one flavor.
- **unhashable statics**: a ``static_argnums`` / ``static_argnames``
  slot fed a list/dict/set literal — unhashable statics miss the
  cache on every single call.

Intentional drift (tests, escape hatches) is blessed with
``# retrace-ok: <reason>``.  The static pass is cross-checked at
runtime by the compile ledger (``gubernator_tpu/compileledger.py``):
what this pass proves about call sites, the ledger proves about the
live process — zero steady-state recompiles after warmup.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Tuple

from . import Violation
from .engine import LintContext, unparse

PASS_ID = "retrace"

_NP_SCALARS = {"int8", "int16", "int32", "int64", "uint8", "uint16",
               "uint32", "uint64", "float16", "float32", "float64",
               "bool_"}
_UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
               ast.SetComp)


def _call_name(node: ast.Call) -> str:
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return ""


def _static_spec(call: ast.Call):
    """(static positions, static names) declared on a jit(...) call."""
    pos, names = set(), set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                pos.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                pos.update(e.value for e in v.elts
                           if isinstance(e, ast.Constant)
                           and isinstance(e.value, int))
        elif kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                names.update(e.value for e in v.elts
                             if isinstance(e, ast.Constant)
                             and isinstance(e.value, str))
    return pos, names


def _kind(arg: ast.AST) -> Optional[str]:
    """Static dtype classification of a call argument, None = dynamic
    (an array / variable whose dtype this pass cannot see)."""
    if isinstance(arg, ast.Constant):
        if isinstance(arg.value, bool):
            return "py-bool"
        if isinstance(arg.value, int):
            return "py-int"
        if isinstance(arg.value, float):
            return "py-float"
        return None
    if isinstance(arg, ast.UnaryOp):
        return _kind(arg.operand)
    if isinstance(arg, ast.Call):
        f = arg.func
        if isinstance(f, ast.Name) and f.id in ("int", "float", "bool"):
            return f"py-{f.id}"
        if isinstance(f, ast.Attribute) and f.attr in _NP_SCALARS:
            return f.attr
    return None


def _blessed(sf, line: int) -> bool:
    return bool(sf.annotation(line, "retrace-ok")
                or sf.annotation(line - 1, "retrace-ok"))


def run(ctx: LintContext) -> List[Violation]:
    out: List[Violation] = []
    for sf in ctx.core_files():
        jitted: Dict[str, Tuple[set, set, int]] = {}
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            if not (isinstance(v, ast.Call) and _call_name(v) == "jit"):
                continue
            pos, names = _static_spec(v)
            for tgt in node.targets:
                jitted[unparse(tgt).replace(" ", "")] = (
                    pos, names, node.lineno)
        if not jitted:
            continue
        # (callable, position) -> {kind: [lines]}
        seen: Dict[Tuple[str, int], Dict[str, List[int]]] = {}
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            ftext = unparse(node.func).replace(" ", "")
            if ftext not in jitted:
                continue
            static_pos, static_names, decl_line = jitted[ftext]
            if node.lineno == decl_line:
                continue  # the jit(...) binding itself
            for i, arg in enumerate(node.args):
                if i in static_pos and isinstance(arg, _UNHASHABLE):
                    if not _blessed(sf, node.lineno):
                        out.append(Violation(
                            sf.rel, node.lineno, PASS_ID,
                            f"unhashable static argument "
                            f"{unparse(arg)[:40]!r} at position {i} of "
                            f"jit callable '{ftext}' — misses the jit "
                            f"cache and recompiles on EVERY call "
                            f"(bless with '# retrace-ok: <reason>')"))
                    continue
                k = _kind(arg)
                if k is not None:
                    seen.setdefault((ftext, i), {}) \
                        .setdefault(k, []).append(node.lineno)
            for kw in node.keywords:
                if kw.arg in static_names and \
                        isinstance(kw.value, _UNHASHABLE) and \
                        not _blessed(sf, node.lineno):
                    out.append(Violation(
                        sf.rel, node.lineno, PASS_ID,
                        f"unhashable static argument {kw.arg}= of jit "
                        f"callable '{ftext}' — misses the jit cache "
                        f"and recompiles on EVERY call (bless with "
                        f"'# retrace-ok: <reason>')"))
        for (ftext, i), kinds in sorted(seen.items()):
            if len(kinds) < 2:
                continue
            lines = sorted(ln for ls in kinds.values() for ln in ls)
            if any(_blessed(sf, ln) for ln in lines):
                continue
            out.append(Violation(
                sf.rel, lines[-1], PASS_ID,
                f"dtype drift at position {i} of jit callable "
                f"'{ftext}': call sites (lines "
                f"{', '.join(map(str, lines))}) pass "
                f"{' vs '.join(sorted(kinds))} — each flavor compiles "
                f"its own program; alternating callers recompile per "
                f"wave (pin one dtype, e.g. np.int64(...), or bless "
                f"with '# retrace-ok: <reason>')"))
    return out
