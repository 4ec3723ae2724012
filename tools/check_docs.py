#!/usr/bin/env python3
"""Docs health check, run by CI next to the tier-1 tests.

Five gates:

1. Markdown link check: every relative link in README.md, ROADMAP.md,
   and docs/**.md must resolve to a file in the repo (anchors are
   stripped; absolute http(s)/mailto links are not fetched).
2. Paper-section check: every module under src/repro/core/ must have a
   module docstring that names the paper section/figure/table it
   implements (the repo's fidelity-audit convention; docs/paper-map.md
   is the cross-reference table built on it).
3. Operator-knob check: every public ``configure_*`` method on
   ``SimCluster`` and ``Fabric`` must be mentioned somewhere under
   docs/ — an undocumented knob is an unusable knob.
4. Trace-taxonomy check: every ``EventKind`` member in
   ``repro.obs.trace`` must appear (by its value string) in
   docs/observability.md — an event type nobody can look up is noise
   in every exported trace.
5. Span-name check: every name in ``repro.obs.host.SPAN_NAMES`` must
   appear in docs/observability.md, for the same reason: each one lands
   in every profiler trace of the chip path.

Exit code 0 iff all gates pass; failures are listed one per line.
"""
from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# [text](target) — target group; images (![...]) match the same shape
_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
# inline/fenced code spans are stripped before link extraction
_FENCE = re.compile(r"```.*?```", re.S)
_CODE = re.compile(r"`[^`]*`")
# a paper anchor: §N, Fig. N, Table N, or Listing N
_PAPER_REF = re.compile(r"§\s*\d|Fig\.\s*\d|Table\s*\d|Listing\s*\d")


def md_files():
    for p in (ROOT / "README.md", ROOT / "ROADMAP.md"):
        if p.exists():
            yield p
    yield from sorted((ROOT / "docs").glob("**/*.md"))


def check_links() -> list:
    errors = []
    for md in md_files():
        text = _CODE.sub("", _FENCE.sub("", md.read_text()))
        for target in _LINK.findall(text):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            rel = target.split("#", 1)[0]
            if not rel:
                continue
            resolved = (md.parent / rel).resolve()
            if not resolved.exists():
                errors.append(f"{md.relative_to(ROOT)}: broken link "
                              f"-> {target}")
    return errors


def check_core_docstrings() -> list:
    errors = []
    for py in sorted((ROOT / "src/repro/core").glob("*.py")):
        if py.name == "__init__.py":
            continue
        doc = ast.get_docstring(ast.parse(py.read_text()))
        if not doc:
            errors.append(f"{py.relative_to(ROOT)}: missing module "
                          f"docstring")
        elif not _PAPER_REF.search(doc):
            errors.append(f"{py.relative_to(ROOT)}: module docstring "
                          f"names no paper section (§N / Fig. N / "
                          f"Table N / Listing N)")
    return errors


# the operator surfaces whose configure_* knobs must be documented
_KNOB_CLASSES = {
    "src/repro/runtime/cluster.py": "SimCluster",
    "src/repro/core/transport.py": "Fabric",
    "src/repro/orchestrator/orchestrator.py": "Orchestrator",
}


def configure_knobs():
    """(class_name, method_name) for every public configure_* method on
    the operator-surface classes."""
    out = []
    for rel, cls_name in _KNOB_CLASSES.items():
        tree = ast.parse((ROOT / rel).read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef) and node.name == cls_name:
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) \
                            and item.name.startswith("configure_"):
                        out.append((cls_name, item.name))
    return out


def check_configure_knobs(knobs) -> list:
    docs_text = "\n".join(p.read_text()
                          for p in sorted((ROOT / "docs").glob("**/*.md")))
    errors = []
    if not knobs:
        errors.append("knob check found no configure_* methods — "
                      "did SimCluster/Fabric move?")
    for cls_name, name in knobs:
        if name not in docs_text:
            errors.append(f"{cls_name}.{name}: operator knob not "
                          f"mentioned anywhere under docs/")
    return errors


def event_kinds():
    """Value strings of every EventKind member in repro.obs.trace,
    read via AST so the check needs no importable package."""
    tree = ast.parse((ROOT / "src/repro/obs/trace.py").read_text())
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "EventKind":
            for item in node.body:
                if isinstance(item, ast.Assign) \
                        and isinstance(item.value, ast.Constant) \
                        and isinstance(item.value.value, str):
                    out.append(item.value.value)
    return out


def check_event_taxonomy(kinds) -> list:
    doc = ROOT / "docs/observability.md"
    if not doc.exists():
        return ["docs/observability.md missing (the trace-event "
                "taxonomy reference)"]
    text = doc.read_text()
    errors = []
    if not kinds:
        errors.append("taxonomy check found no EventKind members — "
                      "did repro.obs.trace move?")
    for kind in kinds:
        if kind not in text:
            errors.append(f"EventKind {kind!r} not documented in "
                          f"docs/observability.md")
    return errors


def span_names():
    """The strings of ``SPAN_NAMES`` in repro.obs.host, each a module
    constant, read via AST so the check needs no importable package."""
    tree = ast.parse((ROOT / "src/repro/obs/host.py").read_text())
    consts, listed = {}, []
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            name, value = node.targets[0].id, node.value
            if isinstance(value, ast.Constant) \
                    and isinstance(value.value, str):
                consts[name] = value.value
            elif name == "SPAN_NAMES" and isinstance(value, ast.Tuple):
                listed = [e.id for e in value.elts
                          if isinstance(e, ast.Name)]
    return [consts[n] for n in listed if n in consts]


def check_span_names(names) -> list:
    doc = ROOT / "docs/observability.md"
    if not doc.exists():
        return ["docs/observability.md missing (the span-name reference)"]
    text = doc.read_text()
    errors = []
    if not names:
        errors.append("span-name check found no SPAN_NAMES — did "
                      "repro.obs.host move?")
    for name in names:
        if f"`{name}`" not in text:
            errors.append(f"span {name!r} not documented in "
                          f"docs/observability.md")
    return errors


def main() -> int:
    knobs = configure_knobs()
    kinds = event_kinds()
    spans = span_names()
    errors = (check_links() + check_core_docstrings()
              + check_configure_knobs(knobs)
              + check_event_taxonomy(kinds)
              + check_span_names(spans))
    for e in errors:
        print(f"FAIL: {e}")
    n_md = len(list(md_files()))
    n_py = len(list((ROOT / "src/repro/core").glob("*.py"))) - 1
    if not errors:
        print(f"docs OK: {n_md} markdown files linked, "
              f"{n_py} core modules cite their paper section, "
              f"{len(knobs)} configure_* knobs documented, "
              f"{len(kinds)} trace-event kinds documented, "
              f"{len(spans)} host span names documented")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
