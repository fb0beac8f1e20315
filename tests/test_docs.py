"""The documents name what exists: every back-ticked repository path in a
document is in the tree, and every row of a ``| flag | default |`` table
names a registered flag with the default the table states."""

import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
DOCS = (["README.md", ".claude/skills/verify/SKILL.md"]
        + sorted(str(p.relative_to(ROOT)) for p in (ROOT / "docs").glob("*.md")))

# a token is taken for a path of this repository when it starts with one of
# its top-level directories, or has a directory part and a source suffix
_ROOTED = ("tools/", "docs/", "tests/", "benchmark/", "baikaldb_tpu/")
_SUFFIXES = (".py", ".md", ".json", ".jsonl", ".txt", ".cpp")
# roots of the upstream BaikalDB tree, which the documents cite beside ours
_UPSTREAM = ("src/", "include/", "test/", "sysbench/", "conf/")


def _paths(text: str):
    for tok in re.findall(r"`([^`\s]+)[^`\n]*`", text):
        tok = re.sub(r":[0-9,:\-]+$", "", tok.split("::")[0])  # `f.py:12-30`
        if re.search(r"[<>*{}$…=()\[\]]|\.\.\.", tok):
            continue                                   # a pattern, not a path
        if tok.startswith(("/", "~", "http", "-")) or tok.startswith(_UPSTREAM):
            continue
        if tok.startswith(_ROOTED) or ("/" in tok and tok.endswith(_SUFFIXES)):
            yield tok


def _exists(tok: str) -> bool:
    if tok.startswith(_ROOTED):
        return (ROOT / tok).exists()
    # `exec/session.py`: the documents name the package's files from its root
    return (ROOT / tok).exists() or (ROOT / "baikaldb_tpu" / tok).exists()


def _flag_rows(text: str):
    in_table = False
    for line in text.splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if not line.lstrip().startswith("|"):
            in_table = False
        elif [c.lower() for c in cells[:2]] == ["flag", "default"]:
            in_table = True
        elif in_table and not set(cells[0]) <= set("-: "):
            yield cells[0].strip("`"), cells[1].strip("`")


def _same(stated: str, default) -> bool:
    if isinstance(default, bool):
        return stated in (("1", "true", "on") if default else ("0", "false", "off"))
    if isinstance(default, str):
        return stated.strip("\"'") == default
    try:
        return float(stated.replace("_", "").replace(",", "")) == float(default)
    except ValueError:
        return False


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_what_exists(doc, all_flags):
    defaults = all_flags.defaults()
    text = (ROOT / doc).read_text()
    missing = sorted({t for t in _paths(text) if not _exists(t)})
    assert missing == [], f"{doc} names paths that are not in the tree"
    wrong = [(name, stated, defaults.get(name, "<no such flag>"))
             for name, stated in _flag_rows(text)
             if name not in defaults or not _same(stated, defaults[name])]
    assert wrong == [], f"{doc}: (flag, default stated, default defined)"
