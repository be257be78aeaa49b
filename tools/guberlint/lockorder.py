"""lockorder — no lexically nested acquisition against the hierarchy.

LOCK_ORDER declares the repo's lock hierarchy, outermost first (the
operator-facing copy lives in CONCURRENCY.md and is cross-checked by
tools/check_metrics.py).  Inside one function, a ``with`` acquiring
lock B while a ``with`` holding lock A is open is legal only when B
ranks STRICTLY deeper than A; acquiring the same rank twice is flagged
as well (``threading.Lock`` is not reentrant).

The analysis is lexical (one function at a time): cross-function
chains — e.g. the dispatcher holding ``_engine_lock`` while the engine
takes ``XLA_EXEC_MU`` — are the hierarchy's *documentation* duty, not
this pass's.  That is exactly the race detector trade-off the
reference accepts with Go's lock conventions: the checker catches the
regression class it can see deterministically, the convention covers
the rest.
"""
from __future__ import annotations

import ast
import re
from typing import List, Tuple

from . import Violation
from .engine import LintContext, unparse

PASS_ID = "lockorder"

#: The lock hierarchy, OUTERMOST first.  Entries are regexes matched
#: against the normalized text of each ``with`` context expression.
#: Mirror of the CONCURRENCY.md table — keep both in sync (checked by
#: tools/check_metrics.py).
LOCK_ORDER: Tuple[Tuple[str, str], ...] = (
    ("submit_mu", r"^self\._submit_mu$"),
    ("peer_mu", r"^self\._peer_mu$"),
    ("send_cond", r"^self\._cond$"),
    ("engine_lock", r"^self\._engine_lock$"),
    ("xla_exec_mu", r"^XLA_EXEC_MU$"),
    ("tel_mu", r"^self\._tel_mu$"),
    ("leaf_mu", r"^(self|mge|fs|gm)\._mu$"),
)

_COMPILED = [(name, re.compile(pat)) for name, pat in LOCK_ORDER]


def _rank(with_text: str):
    for rank, (name, pat) in enumerate(_COMPILED):
        if pat.match(with_text):
            return rank, name
    return None


class _FnAuditor(ast.NodeVisitor):
    def __init__(self, sf, out: List[Violation]):
        self.sf = sf
        self.out = out
        self.held: List[Tuple[int, str, int]] = []  # (rank, name, line)

    def visit_With(self, node: ast.With) -> None:
        pushed = 0
        for item in node.items:
            text = unparse(item.context_expr).replace(" ", "")
            r = _rank(text)
            if r is None:
                continue
            rank, name = r
            for h_rank, h_name, h_line in self.held:
                if rank <= h_rank:
                    self.out.append(Violation(
                        self.sf.rel, node.lineno, PASS_ID,
                        f"acquires '{name}' (rank {rank}) while "
                        f"holding '{h_name}' (rank {h_rank}, line "
                        f"{h_line}) — violates LOCK_ORDER "
                        f"(outermost-first; see CONCURRENCY.md)"))
            self.held.append((rank, name, node.lineno))
            pushed += 1
        self.generic_visit(node)
        for _ in range(pushed):
            self.held.pop()

    visit_AsyncWith = visit_With

    def _visit_fn(self, node) -> None:
        # nested function: fresh lexical scope — a closure runs later,
        # not under the enclosing with (callbacks, workers)
        saved, self.held = self.held, []
        self.generic_visit(node)
        self.held = saved

    visit_FunctionDef = _visit_fn
    visit_AsyncFunctionDef = _visit_fn


def run(ctx: LintContext) -> List[Violation]:
    out: List[Violation] = []
    for sf in ctx.core_files():
        for node in sf.tree.body:
            _FnAuditor(sf, out).visit(node)
    return out
