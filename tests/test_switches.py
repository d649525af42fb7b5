"""The program's environment switches, counted by a machine.

Every `TEMPO_TPU_*` variable the program reads is one more independently
settable value that tests and benchmark cells would have to cover. The
list below is the whole surface: a PR that adds a switch must edit it
(and README.md's table) and be seen doing so; one that deletes a switch
shrinks both.
"""

import ast
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"TEMPO_TPU_[A-Z0-9_]+")

SWITCHES = [
    "TEMPO_TPU_COLCACHE_MB",
    "TEMPO_TPU_COMPILED",
    "TEMPO_TPU_DEVICE_ENCODE",
    "TEMPO_TPU_FAULTS",
    "TEMPO_TPU_GRAPH_DEVICE",
    "TEMPO_TPU_LIGHTWEIGHT",
    "TEMPO_TPU_METRICS_DEVICE",
    "TEMPO_TPU_NO_PALLAS",
    "TEMPO_TPU_OVERLAP",
    "TEMPO_TPU_PAGEHEAT_EXPORT_DIR",
    "TEMPO_TPU_RESULT_CACHE",
    "TEMPO_TPU_RUNSPACE",
    "TEMPO_TPU_STEP_PARTIALS",
    "TEMPO_TPU_XLA_CACHE",
    "TEMPO_TPU_ZONEMAPS",
]


def _is_environ(node: ast.AST) -> bool:
    """`os.environ` or a bare `environ`."""
    return (isinstance(node, ast.Attribute) and node.attr == "environ") or (
        isinstance(node, ast.Name) and node.id == "environ")


def _names_read(tree: ast.AST) -> set[str]:
    """Switch names the module reads: `os.environ.get(N, ...)`,
    `os.getenv(N, ...)`, `os.environ[N]` and `N in os.environ`."""
    keys: list[ast.AST] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) and node.args:
            f = node.func
            if (f.attr == "get" and _is_environ(f.value)) or f.attr == "getenv":
                keys.append(node.args[0])
        elif isinstance(node, ast.Subscript) and _is_environ(node.value):
            keys.append(node.slice)
        elif isinstance(node, ast.Compare) and any(_is_environ(c) for c in node.comparators):
            keys.append(node.left)
    return {k.value for k in keys
            if isinstance(k, ast.Constant) and isinstance(k.value, str)
            and NAME.fullmatch(k.value)}


@functools.cache
def scan() -> tuple[frozenset, frozenset]:
    """(names read through the environment, names mentioned anywhere) over
    every module of tempo_tpu/."""
    read, mentioned = set(), set()
    for root, _, files in os.walk(os.path.join(REPO, "tempo_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    src = fh.read()
                mentioned |= set(NAME.findall(src))
                read |= _names_read(ast.parse(src))
    return frozenset(read), frozenset(mentioned)


@functools.cache
def readme_rows() -> dict:
    """README.md's switch table: name -> its other three cells (what it
    chooses between, default, who sets it)."""
    rows = {}
    with open(os.path.join(REPO, "README.md")) as fh:
        for line in fh:
            cells = [c.strip() for c in line.strip().strip("|").split("|")]
            m = NAME.fullmatch(cells[0].strip("`"))
            if m:
                rows[m.group()] = cells[1:]
    return rows


@pytest.mark.parametrize("name", SWITCHES)
def test_switch_is_listed_and_documented(name):
    read, mentioned = scan()
    assert read == set(SWITCHES), (
        "tempo_tpu/ reads a TEMPO_TPU_* variable that SWITCHES does not list, "
        f"or lists one nothing reads: {sorted(read ^ set(SWITCHES))}")
    assert mentioned <= read, (
        f"named in tempo_tpu/ but read nowhere: {sorted(mentioned - read)}")
    cells = readme_rows().get(name)
    assert cells is not None, f"{name} has no row in README.md's switch table"
    assert len(cells) == 3 and all(cells), (
        f"README.md's row for {name} wants: chooses between | default | who sets it")
    assert set(readme_rows()) == set(SWITCHES), (
        f"README.md's table and SWITCHES differ: {sorted(set(readme_rows()) ^ set(SWITCHES))}")
