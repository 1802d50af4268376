"""Documentation contracts: the ARCHITECTURE.md Public API table locks
``repro.api.__all__``, every export is documented, and the reference
checker / snippet extractor in ``tools/check_docs.py`` find zero rot."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from tools.check_docs import (DOC_FILES, _dangling_path, check_references,
                              documented_api, extract_snippets)


def test_api_all_matches_documented_surface():
    import repro.api
    text = (ROOT / "docs" / "ARCHITECTURE.md").read_text()
    documented = documented_api(text)
    assert documented, "Public API table missing from ARCHITECTURE.md"
    assert sorted(set(documented)) == sorted(set(repro.api.__all__)), (
        "ARCHITECTURE.md Public API table drifted from repro.api.__all__:\n"
        f"  documented-only: {sorted(set(documented) - set(repro.api.__all__))}\n"
        f"  exported-only:   {sorted(set(repro.api.__all__) - set(documented))}")


def test_every_api_export_importable_and_documented():
    import repro.api
    for name in repro.api.__all__:
        obj = getattr(repro.api, name)      # raises on a broken export
        if callable(obj) or isinstance(obj, type):
            assert (obj.__doc__ or "").strip(), (
                f"repro.api.{name} has no docstring")


def test_docs_have_no_dangling_references():
    problems = []
    for rel in DOC_FILES:
        path = ROOT / rel
        assert path.exists(), f"{rel} missing"
        problems += check_references(path, do_import=True)
    assert not problems, "\n".join(problems)


@pytest.mark.parametrize("tok,dangling", [
    ("tools/check_docs.py", False),
    ("docs/", False),
    ("tools/check_docs.py:1", False),
    ("tools/check_docs.py:1-2", False),
    ("tools/check_docs.py:100000", True),
    ("tools/check_docs.py:1-100000", True),
    ("tests/test_docs.py::test_docs_have_no_dangling_references", False),
    ("tests/test_docs.py::no_such_test", True),
    ("tools/no_such_file.py:1", True),
])
def test_references_to_a_place_in_a_file_are_checked(tok, dangling):
    assert _dangling_path(tok) is dangling


def test_docs_snippets_exist_and_compile():
    # execution happens in CI's docs leg (tools/check_docs.py
    # --run-snippets); tier-1 keeps it cheap and just compiles them
    arch = ROOT / "docs" / "ARCHITECTURE.md"
    readme = ROOT / "README.md"
    snippets = extract_snippets(arch) + extract_snippets(readme)
    assert len(snippets) >= 3, "doc snippets went missing"
    for i, code in snippets:
        compile(code, f"snippet{i}", "exec")
