"""docs — the operator-doc consistency pass family (née
tools/check_metrics.py; that CLI survives as a thin shim).

Asserts, against a fresh ``Metrics()`` registry and the live
declarative catalogs:

1. metric (family) names are unique — duplicate registration is a
   silent dashboard breaker (prometheus_client raises on exact dups,
   but two attributes pointing at lookalike names would not);
2. every registered metric is documented in OBSERVABILITY.md;
3. every ``gubernator_*`` name OBSERVABILITY.md documents actually
   exists — a stale doc is how the metrics.py docstring drifted before;
4. every flight-recorder event ``kind`` emitted through telemetry.py
   (literal first arguments to ``.record(...)`` / ``.record_error(...)``
   / ``._record_event(...)`` anywhere under gubernator_tpu/) appears in
   OBSERVABILITY.md's event table, and vice versa — an undocumented
   event kind is invisible to whoever greps the doc mid-incident;
5. RESILIENCE.md's faultpoint table matches faults.FAULT_POINTS both
   ways (the ``faultcat`` pass pins catalog ↔ code; this pins
   catalog ↔ doc — together the chaos surface can't drift anywhere);
6. CONCURRENCY.md's GUBER_* table matches config.ENV_REGISTRY both
   ways (the ``envreg`` pass pins registry ↔ code), and its
   lock-hierarchy table names every lock in guberlint's LOCK_ORDER;
7. OBSERVABILITY.md's "SLO catalog & burn windows" table matches
   slo.SLO_CATALOG both ways — the declarative SLO registry is an
   operator contract, so an SLO that exists but isn't documented (or
   a documented one that was removed) fails tier-1;
8. OBSERVABILITY.md's "Span catalog" table matches
   tracing.SPAN_CATALOG both ways — same contract for the trace
   plane: a span an operator meets in a waterfall must be in the doc,
   and a doc row must name a span the code can actually emit;
9. tracing.PHASE_CATALOG matches, both ways, the literal names handed
   to ``phase(...)`` anywhere under gubernator_tpu/ AND the first
   column of OBSERVABILITY.md's "Phase catalog" table — a phase is a
   metric label, a /debug/phases row, a profile annotation and a span
   name at once, so an uncatalogued one is invisible four times over.
"""
from __future__ import annotations

import os
import re
import sys
from typing import List

from . import Violation, repo_root

PASS_ID = "docs"

REPO = str(repo_root())

DOC = os.path.join(REPO, "OBSERVABILITY.md")
RESILIENCE_DOC = os.path.join(REPO, "RESILIENCE.md")
CONCURRENCY_DOC = os.path.join(REPO, "CONCURRENCY.md")

#: sample suffixes prometheus_client appends — doc names are family
#: names, but a doc mentioning the exposition form shouldn't fail lint
_SUFFIXES = ("_total", "_created", "_bucket", "_count", "_sum", "_info")


def _canonical(name: str, reg_set) -> str:
    """Map a documented name to its registered family: exact match
    wins; otherwise strip ONE sample suffix if that base is registered
    (family names themselves may legitimately end in _count etc., so a
    blind strip would corrupt real names)."""
    if name in reg_set:
        return name
    for s in _SUFFIXES:
        if name.endswith(s) and name[: -len(s)] in reg_set:
            return name[: -len(s)]
    return name


#: literal event kinds at FlightRecorder call sites.  Variable-kind
#: calls (e.g. global_manager's _record_event(kind, ...) helper body)
#: don't match — their literal call sites do.
_KIND_RX = re.compile(
    r"\.(?:record|record_error|_record_event)\(\s*[\"']([a-z0-9_]+)[\"']")


def emitted_event_kinds(pkg_dir: str) -> set:
    kinds = set()
    for root, _dirs, files in os.walk(pkg_dir):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn), encoding="utf-8") as f:
                kinds.update(_KIND_RX.findall(f.read()))
    return kinds


def documented_event_kinds(doc: str) -> set:
    """Backticked names in the first column of the flight-recorder
    event table (the section between '## Flight recorder' and the next
    '## ' heading); one row may document several kinds."""
    try:
        section = doc.split("## Flight recorder", 1)[1]
    except IndexError:
        return set()
    section = section.split("\n## ", 1)[0]
    kinds = set()
    for line in section.splitlines():
        if not line.startswith("| `"):
            continue
        first_cell = line.split("|")[1]
        kinds.update(re.findall(r"`([a-z0-9_]+)`", first_cell))
    return kinds


def _table_cell_names(doc: str, heading: str, rx: str) -> set:
    """Backticked names matching ``rx`` in the first column of the
    table under ``heading`` (up to the next heading of any level)."""
    try:
        section = doc.split(heading, 1)[1]
    except IndexError:
        return set()
    section = re.split(r"\n#{1,6} ", section, 1)[0]
    names = set()
    for line in section.splitlines():
        if not line.startswith("| `"):
            continue
        first_cell = line.split("|")[1]
        names.update(re.findall(rx, first_cell))
    return names


def faultpoint_doc_problems() -> list:
    """RESILIENCE.md's faultpoint catalog table ↔ faults.FAULT_POINTS."""
    from gubernator_tpu.faults import FAULT_POINTS

    with open(RESILIENCE_DOC, encoding="utf-8") as f:
        doc = f.read()
    documented = _table_cell_names(doc, "### Faultpoint catalog",
                                   r"`([a-z0-9_]+)`")
    problems = []
    for point in sorted(set(FAULT_POINTS) - documented):
        problems.append(
            f"faultpoint {point!r} is in faults.FAULT_POINTS but "
            f"missing from RESILIENCE.md's catalog table")
    for point in sorted(documented - set(FAULT_POINTS)):
        problems.append(
            f"RESILIENCE.md's catalog table documents faultpoint "
            f"{point!r} but faults.FAULT_POINTS has no such point")
    return problems


def slo_catalog_doc_problems() -> list:
    """OBSERVABILITY.md's SLO table ↔ slo.SLO_CATALOG, both ways."""
    from gubernator_tpu.slo import SLO_CATALOG

    with open(DOC, encoding="utf-8") as f:
        doc = f.read()
    documented = _table_cell_names(doc, "## SLO catalog & burn windows",
                                   r"`([a-z0-9_]+)`")
    problems = []
    for name in sorted(set(SLO_CATALOG) - documented):
        problems.append(
            f"SLO {name!r} is in slo.SLO_CATALOG but missing from "
            f"OBSERVABILITY.md's SLO catalog table")
    for name in sorted(documented - set(SLO_CATALOG)):
        problems.append(
            f"OBSERVABILITY.md's SLO catalog table documents {name!r} "
            f"but slo.SLO_CATALOG has no such SLO")
    return problems


def span_catalog_doc_problems() -> list:
    """OBSERVABILITY.md's span-catalog table ↔ tracing.SPAN_CATALOG."""
    from gubernator_tpu.tracing import SPAN_CATALOG

    with open(DOC, encoding="utf-8") as f:
        doc = f.read()
    documented = _table_cell_names(doc, "### Span catalog",
                                   r"`([A-Za-z][A-Za-z0-9_.]*)`")
    problems = []
    for name in sorted(set(SPAN_CATALOG) - documented):
        problems.append(
            f"span {name!r} is in tracing.SPAN_CATALOG but missing "
            f"from OBSERVABILITY.md's span catalog table")
    for name in sorted(documented - set(SPAN_CATALOG)):
        problems.append(
            f"OBSERVABILITY.md's span catalog table documents span "
            f"{name!r} but tracing.SPAN_CATALOG has no such span")
    return problems


#: literal phase names at ``tracing.phase`` call sites, and where a sum
#: is handed straight to a sink's ``observe_phase`` (`worker.gap`)
_PHASE_RX = re.compile(r"\b(?:observe_)?phase\(\s*[\"']([a-z_.]+)[\"']")


def emitted_phase_names(pkg_dir: str) -> set:
    names = set()
    for root, _dirs, files in os.walk(pkg_dir):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn), encoding="utf-8") as f:
                    names.update(_PHASE_RX.findall(f.read()))
    return names


def phase_catalog_doc_problems() -> list:
    """tracing.PHASE_CATALOG ↔ ``phase("…")`` literals in the package
    ↔ OBSERVABILITY.md's phase-catalog table."""
    from gubernator_tpu.tracing import PHASE_CATALOG

    with open(DOC, encoding="utf-8") as f:
        doc = f.read()
    documented = _table_cell_names(doc, "#### Phase catalog",
                                   r"`([a-z][a-z_.]*)`")
    emitted = emitted_phase_names(os.path.join(REPO, "gubernator_tpu"))
    cat = set(PHASE_CATALOG)
    problems = []
    for name in sorted(emitted - cat):
        problems.append(
            f"phase {name!r} is handed to tracing.phase() but missing "
            f"from tracing.PHASE_CATALOG")
    for name in sorted(cat - emitted):
        problems.append(
            f"tracing.PHASE_CATALOG lists phase {name!r} but no "
            f"phase({name!r}) call emits it")
    for name in sorted(cat - documented):
        problems.append(
            f"phase {name!r} is in tracing.PHASE_CATALOG but missing "
            f"from OBSERVABILITY.md's phase catalog table")
    for name in sorted(documented - cat):
        problems.append(
            f"OBSERVABILITY.md's phase catalog table documents phase "
            f"{name!r} but tracing.PHASE_CATALOG has no such phase")
    return problems


def thread_roles_doc_problems() -> list:
    """OBSERVABILITY.md's thread-roles table ↔ tracing.THREAD_ROLES,
    both ways; and each kind of the table ends in a catch-all, so that
    no thread is dropped."""
    from gubernator_tpu.tracing import THREAD_ROLES

    with open(DOC, encoding="utf-8") as f:
        doc = f.read()
    documented = _table_cell_names(doc, "#### Thread roles",
                                   r"`([a-z][a-z-]*)`")
    problems = []
    for role in sorted(set(THREAD_ROLES) - documented):
        problems.append(
            f"thread role {role!r} is in tracing.THREAD_ROLES but "
            f"missing from OBSERVABILITY.md's thread-roles table")
    for role in sorted(documented - set(THREAD_ROLES)):
        problems.append(
            f"OBSERVABILITY.md's thread-roles table documents role "
            f"{role!r} but tracing.THREAD_ROLES has no such role")
    for kind in ("py", "comm"):
        last = [pats for k, pats, _doc in THREAD_ROLES.values()
                if k == kind][-1:]
        if last != [("*",)]:
            problems.append(
                f"tracing.THREAD_ROLES: the last role of kind {kind!r} "
                f"must match every name ('*'), or a thread is dropped")
    return problems


def env_registry_doc_problems() -> list:
    """CONCURRENCY.md's GUBER_* table ↔ config.ENV_REGISTRY, plus its
    lock-hierarchy table ↔ guberlint's LOCK_ORDER."""
    from gubernator_tpu.config import ENV_REGISTRY
    from tools.guberlint.lockorder import LOCK_ORDER

    problems = []
    if not os.path.exists(CONCURRENCY_DOC):
        return [f"{CONCURRENCY_DOC} is missing — the concurrency "
                f"tooling's operator doc"]
    with open(CONCURRENCY_DOC, encoding="utf-8") as f:
        doc = f.read()
    documented = _table_cell_names(doc, "## GUBER_* environment",
                                   r"`(GUBER_[A-Z0-9_]+)`")
    for var in sorted(set(ENV_REGISTRY) - documented):
        problems.append(
            f"env var {var} is in config.ENV_REGISTRY but missing from "
            f"CONCURRENCY.md's GUBER_* table")
    for var in sorted(documented - set(ENV_REGISTRY)):
        problems.append(
            f"CONCURRENCY.md's GUBER_* table documents {var} but "
            f"config.ENV_REGISTRY has no such entry")
    doc_locks = _table_cell_names(doc, "## Lock hierarchy",
                                  r"`([a-z_]+)`")
    for name, _pat in LOCK_ORDER:
        if name not in doc_locks:
            problems.append(
                f"lock {name!r} is in guberlint LOCK_ORDER but missing "
                f"from CONCURRENCY.md's lock-hierarchy table")
    for name in sorted(doc_locks - {n for n, _ in LOCK_ORDER}):
        problems.append(
            f"CONCURRENCY.md's lock-hierarchy table documents lock "
            f"{name!r} but guberlint LOCK_ORDER has no such rank")
    return problems


def metric_catalog_problems() -> list:
    """Checks 1-4: registry uniqueness, metrics ↔ OBSERVABILITY.md,
    event kinds ↔ the flight-recorder table."""
    from gubernator_tpu.metrics import Metrics

    m = Metrics()
    registered = [fam.name for fam in m.registry.collect()]
    problems = []

    dups = {n for n in registered if registered.count(n) > 1}
    if dups:
        problems.append(f"duplicate metric names: {sorted(dups)}")

    with open(DOC, encoding="utf-8") as f:
        doc = f.read()
    reg_set = set(registered)
    # the lookahead drops path-like mentions ("gubernator_tpu/metrics.py")
    documented = {_canonical(n, reg_set) for n in re.findall(
        r"gubernator_[a-z0-9_]+(?![a-z0-9_/.])", doc)}

    for name in sorted(reg_set - documented):
        problems.append(
            f"metric {name!r} is registered in metrics.py but missing "
            f"from OBSERVABILITY.md")
    for name in sorted(documented - reg_set):
        problems.append(
            f"OBSERVABILITY.md documents {name!r} but no such metric "
            f"is registered (stale doc entry)")

    emitted = emitted_event_kinds(os.path.join(REPO, "gubernator_tpu"))
    doc_kinds = documented_event_kinds(doc)
    for kind in sorted(emitted - doc_kinds):
        problems.append(
            f"event kind {kind!r} is emitted via telemetry.py but "
            f"missing from the OBSERVABILITY.md event table")
    for kind in sorted(doc_kinds - emitted):
        problems.append(
            f"OBSERVABILITY.md's event table documents kind {kind!r} "
            f"but nothing emits it (stale doc entry)")
    return problems


def run(ctx) -> List[Violation]:
    """guberlint pass entry point.  The doc checks bind to the REAL
    repo (they import live catalogs and read the operator docs);
    fixture trees exercise the other passes."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    groups = (
        ("OBSERVABILITY.md", metric_catalog_problems),
        ("RESILIENCE.md", faultpoint_doc_problems),
        ("CONCURRENCY.md", env_registry_doc_problems),
        ("OBSERVABILITY.md", slo_catalog_doc_problems),
        ("OBSERVABILITY.md", span_catalog_doc_problems),
        ("OBSERVABILITY.md", phase_catalog_doc_problems),
        ("OBSERVABILITY.md", thread_roles_doc_problems),
    )
    out: List[Violation] = []
    for doc_rel, fn in groups:
        for problem in fn():
            out.append(Violation(doc_rel, 1, PASS_ID, problem))
    return out


def main() -> int:
    """The old tools/check_metrics.py CLI, preserved verbatim in
    behavior: exit 0 when clean; print each violation and exit 1."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    problems = (metric_catalog_problems() + faultpoint_doc_problems()
                + env_registry_doc_problems()
                + slo_catalog_doc_problems()
                + span_catalog_doc_problems()
                + phase_catalog_doc_problems()
                + thread_roles_doc_problems())
    if problems:
        for p in problems:
            print(f"check_metrics: {p}", file=sys.stderr)
        return 1
    from gubernator_tpu.metrics import Metrics
    reg_set = {fam.name for fam in Metrics().registry.collect()}
    emitted = emitted_event_kinds(os.path.join(REPO, "gubernator_tpu"))
    print(f"check_metrics: OK ({len(reg_set)} metrics, "
          f"{len(emitted)} event kinds, all documented)")
    return 0
