#!/usr/bin/env python
"""Documentation checker: dangling references + runnable snippets.

Two jobs, both exposed as functions for the tier-1 test
(``tests/test_docs.py``) and as a CLI for CI's ``docs`` leg:

1. **Reference check** — every backticked token in the checked markdown
   files (README.md, docs/ARCHITECTURE.md, ROADMAP.md) that *looks like*
   a repo path (contains ``/`` or a known file extension) must exist on
   disk, and every dotted ``repro.*`` / ``benchmarks.*`` name must
   resolve to an importable module, optionally walking attributes with
   ``--import``.  Docs rot silently; this gate makes a rename that
   forgets its documentation a CI failure.
2. **Snippet check** — fenced ``python`` blocks whose first line is
   ``# doc-snippet`` are executed (``--run-snippets``), sharing one
   namespace per file in document order, so the examples users copy
   cannot drift from the API.

Exit status is non-zero on any dangling reference or failing snippet.

Usage::

    PYTHONPATH=src python tools/check_docs.py --import --run-snippets
"""

from __future__ import annotations

import argparse
import io
import re
import sys
from contextlib import redirect_stdout
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parent.parent

#: the markdown files under contract
DOC_FILES = ("README.md", "docs/ARCHITECTURE.md", "ROADMAP.md")

#: extensions that mark a backticked token as a file reference
_FILE_EXTS = (".py", ".md", ".json", ".yml", ".yaml", ".toml", ".txt")

#: path-like tokens that intentionally name things outside this repo
#: (related-work idioms quoted from PAPERS.md / the retrieval set)
KNOWN_EXTERNAL = ("benchmark/config", "benchmark/Benchmarks.md")

#: importable roots the dotted-name check recognizes
_MODULE_ROOTS = ("repro", "benchmarks", "tools", "tests")

_TICK = re.compile(r"`([^`\n]+)`")
_FENCE = re.compile(r"```python\n(.*?)```", re.S)


def _is_path_token(tok: str) -> bool:
    # parens mark code (method chains like `a()/b()`), not paths
    if any(c in tok for c in "*<>{}$| ()=") or "://" in tok:
        return False
    if tok.startswith(("/", "~", "-", "#")):
        return False
    return "/" in tok or tok.endswith(_FILE_EXTS)


def _path_candidates(tok: str) -> List[Path]:
    tok = tok.rstrip("/")
    bases = (ROOT, ROOT / "src", ROOT / "src" / "repro",
             ROOT / "benchmarks", ROOT / "docs", ROOT / "tools",
             ROOT / "tests")
    out = []
    for b in bases:
        out.append(b / tok)
        if not tok.endswith(_FILE_EXTS):
            out.append(b / (tok + ".py"))
    return out


#: a place in a file: ``path:line``, ``path:first-last`` or ``path::name``
_LOCATION = re.compile(r"(?P<path>[^:]+)(?::(?P<first>\d+)(?:-(?P<last>\d+))?"
                       r"|::(?P<name>\w+))")


def _dangling_path(tok: str) -> bool:
    """Whether ``tok`` names no file here, or a line past the file's end,
    or a ``def``/``class`` the file does not hold."""
    loc = _LOCATION.fullmatch(tok)
    if not loc:
        return not any(c.exists() for c in _path_candidates(tok))
    files = [c for c in _path_candidates(loc["path"]) if c.is_file()]
    if not files:
        return True
    text = files[0].read_text()
    if loc["name"]:
        return not re.search(rf"^\s*(?:def|class) {loc['name']}\b", text,
                             re.M)
    return int(loc["last"] or loc["first"]) > len(text.splitlines())


def _is_dotted_name(tok: str) -> bool:
    tok = tok.rstrip("()")
    if not re.fullmatch(r"[A-Za-z_][\w.]*", tok) or "." not in tok:
        return False
    return tok.split(".", 1)[0] in _MODULE_ROOTS


def _resolve_dotted(tok: str, do_import: bool) -> str:
    """'' when ``tok`` resolves, else the failure reason."""
    import importlib.util
    parts = tok.rstrip("()").split(".")
    # longest prefix that is a module on disk
    mod_parts = list(parts)
    while mod_parts:
        name = ".".join(mod_parts)
        try:
            if importlib.util.find_spec(name) is not None:
                break
        except (ImportError, ModuleNotFoundError, ValueError):
            pass
        mod_parts.pop()
    if not mod_parts:
        return "no importable module prefix"
    if not do_import:
        return ""
    import importlib
    try:
        obj = importlib.import_module(".".join(mod_parts))
    except Exception as e:                      # pragma: no cover - env issue
        return f"import failed: {e}"
    for attr in parts[len(mod_parts):]:
        if not hasattr(obj, attr):
            return (f"module {'.'.join(mod_parts)} has no attribute "
                    f"{attr!r}")
        obj = getattr(obj, attr)
    return ""


def check_references(path: Path, do_import: bool = False) -> List[str]:
    """Dangling backticked references in one markdown file."""
    text = path.read_text()
    # fenced code blocks are snippets, not references
    text = re.sub(r"```.*?```", "", text, flags=re.S)
    problems = []
    seen = set()
    for tok in _TICK.findall(text):
        tok = tok.strip().rstrip(",;:")
        if tok in seen:
            continue
        seen.add(tok)
        if any(tok.startswith(p) for p in KNOWN_EXTERNAL):
            continue
        if _is_path_token(tok):
            if _dangling_path(tok):
                problems.append(f"{path.name}: dangling path `{tok}`")
        elif _is_dotted_name(tok):
            why = _resolve_dotted(tok, do_import)
            if why:
                problems.append(f"{path.name}: dangling name `{tok}` "
                                f"({why})")
    return problems


def extract_snippets(path: Path) -> List[Tuple[int, str]]:
    """(ordinal, code) for each ``# doc-snippet``-marked python fence."""
    out = []
    for i, code in enumerate(_FENCE.findall(path.read_text())):
        first = code.lstrip().splitlines()[0] if code.strip() else ""
        if first.strip() == "# doc-snippet":
            out.append((i, code))
    return out


def run_snippets(path: Path) -> List[str]:
    """Execute the file's marked snippets in one shared namespace."""
    problems = []
    ns: Dict[str, object] = {"__name__": f"doc_snippet:{path.name}"}
    for i, code in extract_snippets(path):
        try:
            with redirect_stdout(io.StringIO()):
                exec(compile(code, f"{path.name}:snippet{i}", "exec"), ns)
        except Exception as e:
            problems.append(f"{path.name} snippet #{i} raised "
                            f"{type(e).__name__}: {e}")
    return problems


def documented_api(md_text: str) -> List[str]:
    """The export names listed in ARCHITECTURE.md's "Public API" table —
    the surface ``tests/test_docs.py`` locks against
    ``repro.api.__all__``."""
    lines = md_text.splitlines()
    names: List[str] = []
    in_section = False
    for line in lines:
        if line.startswith("#"):
            in_section = "public api" in line.lower()
            continue
        if in_section and line.lstrip().startswith("|"):
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            if not cells or cells[0].startswith("-") or cells[0] in (
                    "Export", "Exports"):
                continue
            for m in _TICK.findall(cells[0]):
                names.extend(n.strip().rstrip("()")
                             for n in m.split(",") if n.strip())
    return names


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--import", dest="do_import", action="store_true",
                    help="resolve dotted names by real import + getattr")
    ap.add_argument("--run-snippets", action="store_true",
                    help="execute # doc-snippet fenced blocks")
    ap.add_argument("files", nargs="*", default=None,
                    help=f"markdown files to check (default: {DOC_FILES})")
    args = ap.parse_args(argv)

    files = [Path(f) for f in (args.files or
                               [ROOT / f for f in DOC_FILES])]
    problems = []
    for path in files:
        if not path.exists():
            problems.append(f"{path}: file missing")
            continue
        problems += check_references(path, do_import=args.do_import)
        if args.run_snippets:
            problems += run_snippets(path)
    for p in problems:
        print(f"FAIL {p}")
    if not problems:
        n = sum(len(extract_snippets(p)) for p in files if p.exists())
        print(f"docs OK: {len(files)} files, {n} snippets")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
