"""Documentation conformance:

  * every ```python block in docs/*.md and README.md executes cleanly
    (the examples in docs/api.md are real, asserted programs);
  * every `file.py:symbol` anchor in docs/paper_map.md points at a file
    that exists and a symbol defined in it (the paper↔code map cannot
    silently rot as the tree is refactored);
  * doctests in the public core modules pass (the CI doctest leg runs
    the full ``--doctest-modules`` sweep; this keeps a fast local
    subset in tier-1).
"""

import doctest
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

_DOC_FILES = sorted(
    p for p in [*(ROOT / "docs").glob("*.md"), ROOT / "README.md"]
    if p.exists()
)


def extract_python_blocks(path: pathlib.Path):
    """All ```python fenced blocks of a markdown file, with line info."""
    text = path.read_text()
    blocks = []
    for m in re.finditer(r"```python\n(.*?)```", text, re.DOTALL):
        line = text[: m.start()].count("\n") + 2
        blocks.append((line, m.group(1)))
    return blocks


_SNIPPETS = [
    pytest.param(path, line, code, id=f"{path.name}:L{line}")
    for path in _DOC_FILES
    for line, code in extract_python_blocks(path)
]


@pytest.mark.parametrize("path,line,code", _SNIPPETS)
def test_doc_snippet_runs(path, line, code):
    """Each doc example is a self-contained program with its own
    assertions; a failure points at <file>:L<line>."""
    namespace = {"__name__": f"docsnippet_{path.stem}_L{line}"}
    exec(compile(code, f"{path.name}:L{line}", "exec"), namespace)


# ----------------------------------------------------------------------
# paper_map.md anchors
# ----------------------------------------------------------------------

_ANCHOR_RE = re.compile(r"`((?:src|tests|benchmarks|examples)/[\w/]+\.py):([A-Za-z_]\w*)`")


def _paper_map_anchors():
    text = (ROOT / "docs" / "paper_map.md").read_text()
    anchors = sorted(set(_ANCHOR_RE.findall(text)))
    assert anchors, "docs/paper_map.md must contain file:symbol anchors"
    return anchors


@pytest.mark.parametrize(
    "rel,symbol", _paper_map_anchors(), ids=lambda v: str(v)
)
def test_paper_map_anchor_exists(rel, symbol):
    path = ROOT / rel
    assert path.exists(), f"paper_map.md references missing file {rel}"
    src = path.read_text()
    pattern = re.compile(
        rf"^\s*(?:def|class)\s+{re.escape(symbol)}\b|^{re.escape(symbol)}\s*=",
        re.MULTILINE,
    )
    assert pattern.search(src), (
        f"paper_map.md references {rel}:{symbol}, not defined there"
    )


def test_paper_map_covers_all_nine_steps():
    text = (ROOT / "docs" / "paper_map.md").read_text()
    table = [ln for ln in text.splitlines() if ln.startswith("|")]
    steps = [ln for ln in table if re.match(r"\|\s*[1-9]\s*\|", ln)]
    assert len(steps) == 9, f"expected 9 algorithm-step rows, got {len(steps)}"


# ----------------------------------------------------------------------
# doctests (fast local subset; CI runs the full --doctest-modules leg)
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "module_name",
    ["repro.core.key_codec", "repro.core.bucket_sort",
     "repro.core.partial_sort"],
)
def test_module_doctests(module_name):
    import importlib

    mod = importlib.import_module(module_name)
    results = doctest.testmod(mod, verbose=False)
    assert results.failed == 0, f"{results.failed} doctest failures in {module_name}"
    assert results.attempted > 0, f"no doctests collected from {module_name}"
