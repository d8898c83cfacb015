#!/usr/bin/env python
"""Docs-reference check: fail CI when docs point at files that don't exist.

Scans the backtick code spans of the narrative docs for repo-relative
path-like references (contain a ``/`` or a known suffix) and verifies each
resolves to a real file or directory.  Keeps README.md,
docs/ARCHITECTURE.md and DESIGN.md honest as the tree refactors.

    python scripts/check_docs.py
"""
from __future__ import annotations

import os
import re
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DOCS = ["README.md", "docs/ARCHITECTURE.md", "DESIGN.md"]
SUFFIXES = (".py", ".md", ".sh", ".json", ".yml")

# `code span` that looks like a repo path: has a slash or a known suffix
_CODE = re.compile(r"`([^`\n]+)`")
# markdown links: [text](target)
_LINK = re.compile(r"\]\(([^)#\s]+)\)")


def _candidates(text: str):
    for m in _CODE.finditer(text):
        ref = m.group(1).strip()
        if " " in ref or ref.startswith(("--", "-", "<", "{")):
            continue                      # flags / placeholders, not paths
        if "/" in ref or ref.endswith(SUFFIXES):
            yield ref
    for m in _LINK.finditer(text):
        ref = m.group(1).strip()
        if "://" in ref:
            continue                      # external URL
        yield ref


def check(doc: str) -> list:
    path = os.path.join(ROOT, doc)
    base = os.path.dirname(path)
    missing = []
    with open(path) as f:
        text = f.read()
    for ref in _candidates(text):
        ref = ref.rstrip("/").split("::")[0]
        if not ("/" in ref or ref.endswith(SUFFIXES)):
            continue
        if "*" in ref:
            continue                      # glob pattern, not a single file
        # try: relative to the doc, repo root, src/ and src/repro/ (the
        # narrative docs use `serving/kv_cache.py`-style module shorthand),
        # kernels/ for the kernel packages (`fused_score/kernel.py`), and
        # launch/ for bare entrypoint names
        roots = (base, ROOT, os.path.join(ROOT, "src"),
                 os.path.join(ROOT, "src", "repro"),
                 os.path.join(ROOT, "src", "repro", "kernels"),
                 os.path.join(ROOT, "src", "repro", "launch"))
        if not any(os.path.exists(os.path.normpath(os.path.join(r, ref)))
                   for r in roots):
            missing.append((doc, ref))
    return missing


def main() -> int:
    missing = []
    for doc in DOCS:
        if not os.path.exists(os.path.join(ROOT, doc)):
            missing.append(("<tree>", doc))
            continue
        missing.extend(check(doc))
    if missing:
        print("docs reference files that do not exist:")
        for doc, ref in missing:
            print(f"  {doc}: {ref}")
        return 1
    print(f"docs check OK ({', '.join(DOCS)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
