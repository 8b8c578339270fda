#!/usr/bin/env python
"""Docs-health check (CI + ``make docs-check``): fail on broken relative
links in README.md / docs/*.md, and assert the README's verify commands
match the Makefile's targets (so the quickstart can never drift from what
CI actually runs).

Stdlib-only on purpose — runs before any deps are installed.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# [text](target) — excluding images' inner brackets is unnecessary; the
# pattern also matches ![alt](target), which we want checked too.
_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_SCHEMES = ("http://", "https://", "mailto:")


def doc_files():
    files = [ROOT / "README.md"]
    files += sorted((ROOT / "docs").glob("*.md"))
    return [f for f in files if f.exists()]


def check_links() -> list:
    errors = []
    for doc in doc_files():
        text = doc.read_text()
        for m in _LINK.finditer(text):
            target = m.group(1)
            if target.startswith(_SCHEMES):
                continue
            path = target.split("#", 1)[0]
            if not path:            # pure in-page anchor
                continue
            resolved = (doc.parent / path).resolve()
            if not resolved.exists():
                errors.append(f"{doc.relative_to(ROOT)}: broken link "
                              f"-> {target}")
    return errors


def _makefile_recipe(target: str) -> list:
    """Recipe lines (tab-indented) of a Makefile target, '' if absent."""
    lines = (ROOT / "Makefile").read_text().splitlines()
    out, active = [], False
    for line in lines:
        if re.match(rf"^{re.escape(target)}\s*:", line):
            active = True
            out.append(line)
            continue
        if active:
            if line.startswith("\t"):
                out.append(line.strip())
            else:
                break
    return out


def check_verify_commands() -> list:
    errors = []
    readme = (ROOT / "README.md").read_text()
    verify = _makefile_recipe("verify")
    if not verify:
        errors.append("Makefile: no `verify` target")
    if "make verify" not in readme:
        errors.append("README.md: quickstart must mention `make verify` "
                      "(the tier-1 entry point)")
    # the README's bare test command must match the Makefile's `test`
    # recipe (modulo the $(PY) indirection)
    test = " ".join(_makefile_recipe("test")[1:2])
    test_cmd = test.replace("$(PY)", "python").strip()
    if test_cmd and test_cmd not in readme:
        errors.append(f"README.md: test command drifted from Makefile "
                      f"`test` target ({test_cmd!r} not found)")
    return errors


_PY_TOKEN = re.compile(r"`([\w./-]+\.py)`")
# bare module names in the README must exist under one of these trees
_CODE_DIRS = ("src", "tools", "benchmarks", "examples")


def check_module_map() -> list:
    """Every backtick-quoted ``*.py`` token in README.md must reference a
    real file: path-qualified tokens resolve from the repo root; bare
    names must exist at the root or somewhere under the code trees. Keeps
    the module-map table honest when files are renamed or split."""
    errors = []
    readme = ROOT / "README.md"
    bare_index = None
    for token in sorted(set(_PY_TOKEN.findall(readme.read_text()))):
        if "/" in token:
            if not (ROOT / token).exists():
                errors.append(f"README.md: module-map references missing "
                              f"file `{token}`")
            continue
        if bare_index is None:
            bare_index = {p.name for d in _CODE_DIRS
                          for p in (ROOT / d).rglob("*.py")}
            bare_index |= {p.name for p in ROOT.glob("*.py")}
        if token not in bare_index:
            errors.append(f"README.md: `{token}` not found at the repo root "
                          f"or under any of {'/'.join(_CODE_DIRS)}")
    return errors


def main() -> int:
    errors = check_links() + check_verify_commands() + check_module_map()
    docs = ", ".join(str(f.relative_to(ROOT)) for f in doc_files())
    if errors:
        print(f"docs-check FAILED ({docs}):")
        for e in errors:
            print(f"  - {e}")
        return 1
    print(f"docs-check OK: {docs}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
