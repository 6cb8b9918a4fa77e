"""Every independently settable value is counted: the ``CHAINERMN_TPU_*``
names the package reads are the literal list below and no other, and each
has its row (name, default, what reads it) in ``docs/api.md``'s
"Environment switches" table.  A PR that adds a switch edits this list
in plain sight; one that deletes a switch deletes its line here.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SWITCHES = (
    "CHAINERMN_TPU_AUTOTUNE_DIR",
    "CHAINERMN_TPU_BUCKET_MB",
    "CHAINERMN_TPU_COMPRESS",
    "CHAINERMN_TPU_FAULT_SCHEDULE",
    "CHAINERMN_TPU_FLASH_INTERPRET",
    "CHAINERMN_TPU_FLEET",
    "CHAINERMN_TPU_FORCE_ABORT_ON_EXCEPTION",
    "CHAINERMN_TPU_HIERARCHY",
    "CHAINERMN_TPU_MAXPOOL_VJP",
    "CHAINERMN_TPU_PAGED_ATTN",
    "CHAINERMN_TPU_SERVE_DISAGG",
    "CHAINERMN_TPU_SERVE_SPEC",
    "CHAINERMN_TPU_STRIPE_RATIO",
    "CHAINERMN_TPU_TRACE",
    "CHAINERMN_TPU_TRACE_CAPACITY",
)


@pytest.fixture(scope="module")
def names_in_the_package():
    """{name: [files]} over ``chainermn_tpu/**/*.py``, code, comments and
    docstrings alike: a name the package only talks about is one a
    reader will try to set."""
    found = {}
    for base, dirs, files in os.walk(os.path.join(ROOT, "chainermn_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(base, name)
            with open(path) as f:
                for switch in re.findall(r"CHAINERMN_TPU_[A-Z_0-9]+",
                                         f.read()):
                    found.setdefault(switch, []).append(
                        os.path.relpath(path, ROOT))
    return found


@pytest.fixture(scope="module")
def documented_rows():
    with open(os.path.join(ROOT, "docs", "api.md")) as f:
        text = f.read()
    table = text.split("## Environment switches", 1)[1].split("\n## ", 1)[0]
    return {m.group(1): [c.strip() for c in m.group(2).split("|")]
            for m in re.finditer(r"^\| `(CHAINERMN_TPU_[A-Z_0-9]+)` \|(.*)\|$",
                                 table, re.M)}


@pytest.mark.parametrize("name", SWITCHES)
def test_switch_is_listed_and_documented(name, names_in_the_package,
                                         documented_rows):
    assert name in names_in_the_package, \
        f"{name} is listed but nothing in chainermn_tpu/ reads it"
    assert name in documented_rows, \
        f"{name} has no row in docs/api.md's table"
    default, reader = documented_rows[name]
    assert default and reader


def test_no_switch_outside_the_list(names_in_the_package, documented_rows):
    extra = {k: v for k, v in names_in_the_package.items()
             if k not in SWITCHES}
    assert extra == {}, (
        f"the package names switches this list does not: {extra}")
    assert sorted(documented_rows) == sorted(SWITCHES)
