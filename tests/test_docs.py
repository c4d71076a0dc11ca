"""Docs hygiene: every relative markdown link in README/docs/*.md resolves.

Runs the same check CI's "Docs link check" step runs
(``scripts/check_doc_links.py``), so a broken link fails tier-1 locally
before it fails CI.
"""

import importlib.util
import pathlib

_SCRIPT = pathlib.Path(__file__).resolve().parent.parent / "scripts" / "check_doc_links.py"


def _load_checker():
    spec = importlib.util.spec_from_file_location("check_doc_links", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_markdown_relative_links_resolve():
    mod = _load_checker()
    errors = mod.check()
    assert not errors, "broken doc links:\n" + "\n".join(errors)


def test_checker_covers_the_core_docs():
    mod = _load_checker()
    names = {p.name for p in mod._doc_files()}
    assert {"README.md", "PERF.md", "ARCHITECTURE.md", "SERVING.md"} <= names
