#!/usr/bin/env python3
"""Docs sanity checker (run by the CI docs job).

- Fenced ``python`` blocks in README.md / docs/*.md / src/**/README.md
  must compile (syntax-valid snippets).
- Fenced ``bash`` blocks must shlex-parse line by line (no mangled
  commands in quickstarts).
- Relative markdown links must resolve to files in the repo.
- No ``*.pyc`` / ``__pycache__`` files may be tracked by git — checked
  against both the file list and the HEAD tree, so a committed
  ``__pycache__`` *directory* fails even if its files were filtered.
- ``benchmarks/__pycache__/`` must be gitignored (the bench runners
  drop bytecode next to the committed BENCH_*.json snapshots).
- Public-API doc coverage: every public module / class / function /
  method in ``src/repro/core``, ``src/repro/service``,
  ``src/repro/fabric`` and ``src/repro/obs`` must carry a docstring
  (the packages tenants program against stay documented).
- Contract coverage: every public top-level symbol of
  ``src/repro/core/backend.py`` and of the event_filter kernel surface
  (``src/repro/kernels/event_filter/ops.py``) must be mentioned
  by name in ``docs/backends.md``, every public top-level symbol of the
  ``src/repro/obs`` modules in ``docs/observability.md``, and every
  public top-level symbol of ``src/repro/service/policy.py`` in
  ``docs/policy.md`` — adding an API without documenting the contract
  fails CI.

Exits non-zero with a per-finding report on any violation.
"""
from __future__ import annotations

import ast
import pathlib
import re
import shlex
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
FENCE_RE = re.compile(r"^```(\w*)\s*$")
LINK_RE = re.compile(r"\[[^\]]+\]\(([^)#]+)(?:#[^)]*)?\)")
API_PACKAGES = ("src/repro/core", "src/repro/service", "src/repro/fabric",
                "src/repro/obs")


def doc_files():
    out = [ROOT / "README.md"]
    out += sorted((ROOT / "docs").glob("*.md"))
    out += sorted((ROOT / "src").rglob("README.md"))
    return [p for p in out if p.exists()]


def fenced_blocks(text):
    """Yield (language, start_line, block_text) for each fenced block."""
    lang, start, buf = None, 0, []
    for i, line in enumerate(text.splitlines(), 1):
        m = FENCE_RE.match(line.strip())
        if m and lang is None:
            lang, start, buf = m.group(1) or "text", i, []
        elif line.strip() == "```" and lang is not None:
            yield lang, start, "\n".join(buf)
            lang = None
        elif lang is not None:
            buf.append(line)


def check_file(path):
    errors = []
    text = path.read_text()
    rel = path.relative_to(ROOT)
    for lang, line, block in fenced_blocks(text):
        if lang == "python":
            try:
                compile(block, f"{rel}:{line}", "exec")
            except SyntaxError as e:
                errors.append(f"{rel}:{line}: python block fails to "
                              f"compile: {e}")
        elif lang in ("bash", "sh", "shell"):
            for off, cmd in enumerate(block.splitlines()):
                cmd = cmd.strip()
                if not cmd or cmd.startswith("#"):
                    continue
                try:
                    shlex.split(cmd.rstrip("\\"))
                except ValueError as e:
                    errors.append(f"{rel}:{line + off}: bash line does "
                                  f"not parse: {e}")
    for m in LINK_RE.finditer(text):
        target = m.group(1).strip()
        if "://" in target or target.startswith("mailto:"):
            continue
        resolved = (path.parent / target).resolve()
        if not resolved.exists():
            errors.append(f"{rel}: broken relative link: {target}")
    return errors


def check_api_docs():
    """Undocumented public symbols in the API packages (see module doc).

    Public = not underscore-prefixed; covered: the module itself,
    top-level classes and functions, and methods of public classes."""
    errors = []
    for pkg in API_PACKAGES:
        for path in sorted((ROOT / pkg).glob("*.py")):
            rel = path.relative_to(ROOT)
            tree = ast.parse(path.read_text())
            if not ast.get_docstring(tree):
                errors.append(f"{rel}:1: public module lacks a docstring")
            for node in tree.body:
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef,
                                         ast.ClassDef)):
                    continue
                if node.name.startswith("_"):
                    continue
                if not ast.get_docstring(node):
                    errors.append(f"{rel}:{node.lineno}: public "
                                  f"{node.name!r} lacks a docstring")
                if isinstance(node, ast.ClassDef):
                    for sub in node.body:
                        if (isinstance(sub, (ast.FunctionDef,
                                             ast.AsyncFunctionDef))
                                and not sub.name.startswith("_")
                                and not ast.get_docstring(sub)):
                            errors.append(
                                f"{rel}:{sub.lineno}: public method "
                                f"{node.name}.{sub.name} lacks a docstring")
    return errors


def _contract_doc_errors(sources, doc_rel):
    """Every public top-level name (classes, functions, UPPERCASE
    constants) in ``sources`` must appear in the contract doc
    ``doc_rel``."""
    doc = ROOT / doc_rel
    if not doc.exists():
        return [f"contract doc {doc_rel} is missing"]
    text = doc.read_text()
    errors = []
    for src in sources:
        for node in ast.parse(src.read_text()).body:
            names = []
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                names = [node.name]
            elif isinstance(node, ast.Assign):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name) and t.id.isupper()]
            for name in names:
                if name.startswith("_"):
                    continue
                if not re.search(rf"\b{re.escape(name)}\b", text):
                    errors.append(
                        f"{doc_rel}: public symbol {name!r} "
                        f"({src.relative_to(ROOT)}) is undocumented in "
                        f"the contract doc")
    return errors


def check_backend_contract_doc():
    """Every public top-level name in core/backend.py — plus the
    event_filter kernel surface the SPMD backend programs against
    (ops.py recognizers/kernel entry points) — must appear in
    docs/backends.md (see module docstring)."""
    return _contract_doc_errors(
        [ROOT / "src/repro/core/backend.py",
         ROOT / "src/repro/kernels/event_filter/ops.py"],
        "docs/backends.md")


def check_policy_contract_doc():
    """Every public top-level name in service/policy.py must appear in
    docs/policy.md (state machine, thresholds and decision surface stay
    in sync with the code)."""
    return _contract_doc_errors([ROOT / "src/repro/service/policy.py"],
                                "docs/policy.md")


def check_lease_contract_doc():
    """Every public top-level name in fabric/leases.py must appear in
    docs/fabric.md (single-flight lease lifecycle, TTL/failover
    semantics and the adoption surface stay in sync with the code)."""
    return _contract_doc_errors([ROOT / "src/repro/fabric/leases.py"],
                                "docs/fabric.md")


def check_obs_contract_doc():
    """Every public top-level name of the observability package must
    appear in docs/observability.md (span taxonomy / metric catalog /
    health semantics stay in sync with the code)."""
    return _contract_doc_errors(
        sorted((ROOT / "src/repro/obs").glob("*.py")),
        "docs/observability.md")


def check_benchmark_hygiene():
    """``benchmarks/__pycache__/`` must be covered by .gitignore (the
    bench runners import ``benchmarks`` as a package, so running them
    drops bytecode next to the committed BENCH_*.json snapshots — an
    unignored cache dir shows up in every ``git status`` and invites a
    committed-bytecode regression)."""
    probe = subprocess.run(
        ["git", "check-ignore", "-q", "benchmarks/__pycache__/x.pyc"],
        cwd=ROOT, check=False)
    if probe.returncode != 0:
        return ["benchmarks/__pycache__/ is not gitignored "
                "(add it to .gitignore)"]
    return []


def check_no_tracked_pyc():
    """No bytecode in git: neither tracked ``*.pyc``/``__pycache__``
    files, nor a committed ``__pycache__`` directory in the HEAD tree
    (``ls-tree -rd`` sees tree entries that ``ls-files`` can miss)."""
    out = subprocess.run(["git", "ls-files"], cwd=ROOT, check=True,
                         capture_output=True, text=True).stdout
    bad = [f for f in out.splitlines()
           if f.endswith(".pyc") or "__pycache__" in f]
    errors = [f"tracked bytecode must not be committed: {f}" for f in bad]
    tree = subprocess.run(["git", "ls-tree", "-rd", "--name-only", "HEAD"],
                          cwd=ROOT, check=False,
                          capture_output=True, text=True).stdout
    errors += [f"committed __pycache__ directory: {d}"
               for d in tree.splitlines() if d.endswith("__pycache__")]
    return errors


def main() -> int:
    errors = []
    for path in doc_files():
        errors += check_file(path)
    errors += check_no_tracked_pyc()
    errors += check_benchmark_hygiene()
    errors += check_api_docs()
    errors += check_backend_contract_doc()
    errors += check_policy_contract_doc()
    errors += check_lease_contract_doc()
    errors += check_obs_contract_doc()
    if errors:
        print(f"check_docs: {len(errors)} problem(s)")
        for e in errors:
            print(f"  {e}")
        return 1
    print(f"check_docs: OK ({len(doc_files())} docs checked, "
          f"no tracked bytecode, public API of "
          f"{'+'.join(p.split('/')[-1] for p in API_PACKAGES)} documented)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
