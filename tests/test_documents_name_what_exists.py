"""The documents that give commands give commands that exist.

``test_document[<file>]``: in a tracked document, every COMMAND resolves,
and nothing names the measurement stack PR 29 deleted.  The rule is
commands only, read from inline code spans and fenced blocks (for the
``Makefile``: from its recipes):

* ``python <path>.py`` / ``bash <path>.sh``: the file exists, from the
  repository's root or from the document's own directory;
* ``python -m <module>``: the module can be found;
* ``make <target>``: the ``Makefile`` has the target.

Not every backticked path: documents name package-relative paths and
output files loosely, and chasing those is not this test.

``test_tool_is_listed_and_tested[<tool>]``: every ``tools/*.py`` has a
row in ``tools/README.md`` and a test file that imports or runs it, so an
instrument nothing reads cannot come back unnoticed.
"""

import glob
import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md", "PARITY.md", "Makefile", "tools/README.md",
              "examples/README.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, ROOT).replace(os.sep, "/")
                      for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))))

#: what PR 29 deleted: no ``BENCH_*`` knob and none of these files
RETIRED = re.compile(
    r"BENCH_[A-Z_]+|\bbench\.py|bench_scaling|probe_perf|profile_tpu_step"
    r"|flash_block_sweep|\bbench_input\b|MULTICHIP_r0")

_SCRIPT = re.compile(r"(?:python3?|bash|\$\(PY\))\s+(?:-[A-Za-z]\s+)*"
                     r"([\w./-]+\.(?:py|sh))\b")
_MODULE = re.compile(r"(?:python3?|\$\(PY\))\s+-m\s+([\w.]+)")
_MAKE = re.compile(r"\bmake\s+([a-z][\w-]*)")


def _read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


def _make_targets():
    return set(re.findall(r"^([a-z][\w-]*):", _read("Makefile"), re.M))


def _code(rel, text):
    """The text commands are read from: a Makefile's recipe lines, a
    document's fenced blocks and inline code spans."""
    if rel == "Makefile":
        return "\n".join(l for l in text.splitlines() if l.startswith("\t"))
    fenced = re.findall(r"```.*?\n(.*?)```", text, re.S)
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"```.*?```", "", text,
                                                flags=re.S))
    return "\n".join(fenced + inline)


@pytest.mark.parametrize("rel", DOCUMENTS)
def test_document(rel):
    text = _read(rel)
    assert RETIRED.findall(text) == [], rel
    code = _code(rel, text)
    here = os.path.dirname(os.path.join(ROOT, rel))
    missing = [p for p in _SCRIPT.findall(code)
               if not (os.path.isfile(os.path.join(ROOT, p))
                       or os.path.isfile(os.path.join(here, p)))]
    assert missing == [], f"{rel} runs scripts that do not exist"
    unknown = [m for m in _MODULE.findall(code)
               if importlib.util.find_spec(m) is None]
    assert unknown == [], f"{rel} runs modules that cannot be found"
    if rel != "Makefile":
        stray = sorted(set(_MAKE.findall(code)) - _make_targets())
        assert stray == [], f"{rel} names make targets that do not exist"


TOOLS = sorted(os.path.basename(p)
               for p in glob.glob(os.path.join(ROOT, "tools", "*.py")))


@pytest.mark.parametrize("tool", TOOLS)
def test_tool_is_listed_and_tested(tool):
    assert re.search(rf"^\| `{re.escape(tool)}` \|", _read("tools/README.md"),
                     re.M), f"tools/{tool} has no row in tools/README.md"
    name = tool[:-len(".py")]
    users = [p for p in glob.glob(os.path.join(ROOT, "tests", "**", "*.py"),
                                  recursive=True)
             if os.path.abspath(p) != os.path.abspath(__file__)
             and re.search(rf"\b{name}\b", _read(os.path.relpath(p, ROOT)))]
    assert users, f"no test imports or runs tools/{tool}"
