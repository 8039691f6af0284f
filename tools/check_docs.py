#!/usr/bin/env python3
"""Docs reference checker: code references in README.md must resolve.

Checked reference forms (inside backticks):
  `path/to/file.py`            -> file must exist
  `path/to/file.py::symbol`    -> file must exist AND contain `symbol`
  `dir/`                       -> directory must exist
  `python -m pkg.mod ...`      -> module must resolve under src/ (or the
                                  repo root)

Run from anywhere:  python tools/check_docs.py
Exit code 1 on any dangling reference (CI gate).
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DOCS = ["README.md"]

# runtime-generated artifacts: docs may reference them before they exist
ALLOW_MISSING_PREFIXES = ("experiments/",)


def allowed_missing(rel: str) -> bool:
    return rel.startswith(ALLOW_MISSING_PREFIXES)

PATHLIKE = re.compile(r"`([A-Za-z0-9_./-]+\.(?:py|md|toml|yml|txt))(?:::([A-Za-z0-9_.]+))?`")
DIRLIKE = re.compile(r"`([A-Za-z0-9_./-]+/)`")
MODLIKE = re.compile(r"`(?:PYTHONPATH=src )?python -m ([A-Za-z0-9_.]+)")


def module_path(mod: str) -> Path | None:
    for base in (ROOT / "src", ROOT):
        p = base / Path(*mod.split("."))
        if p.with_suffix(".py").exists() or (p / "__init__.py").exists() \
                or (p / "__main__.py").exists() or (p / "run.py").exists():
            return p
    return None


def check(doc: str) -> list[str]:
    text = (ROOT / doc).read_text()
    errors = []
    for m in PATHLIKE.finditer(text):
        rel, symbol = m.group(1), m.group(2)
        path = ROOT / rel
        if not path.exists():
            if not allowed_missing(rel):
                errors.append(f"{doc}: `{m.group(0)[1:-1]}` — missing file {rel}")
            continue
        if symbol:
            leaf = symbol.rsplit(".", 1)[-1]
            if leaf not in path.read_text():
                errors.append(f"{doc}: `{m.group(0)[1:-1]}` — {rel} has no '{leaf}'")
    for m in DIRLIKE.finditer(text):
        rel = m.group(1)
        if "/" in rel.rstrip("/") or rel in ("src/", "tests/", "bench/", "examples/"):
            if not (ROOT / rel).exists() and not allowed_missing(rel):
                errors.append(f"{doc}: `{rel}` — missing directory")
    for m in MODLIKE.finditer(text):
        mod = m.group(1)
        if module_path(mod) is None:
            errors.append(f"{doc}: `python -m {mod}` — module not found under src/ or repo root")
    return errors


def main() -> int:
    missing_docs = [d for d in DOCS if not (ROOT / d).exists()]
    errors = [f"missing doc: {d}" for d in missing_docs]
    for doc in DOCS:
        if doc not in missing_docs:
            errors.extend(check(doc))
    if errors:
        print("DOCS CHECK FAILED:")
        for e in errors:
            print("  " + e)
        return 1
    print(f"docs check OK: all code references in {', '.join(DOCS)} resolve")
    return 0


if __name__ == "__main__":
    sys.exit(main())
