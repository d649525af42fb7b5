"""The architecture, stated once where a machine reads it.

tempo_tpu's top-level packages are ranked in layers; an import may point
sideways or down, and the few that point up are named in UPWARD
(ROADMAP.md C15, edge for edge, with the move each wants). Imports inside
functions count: most of these hide there because at module level they
would be cycles. The list only shrinks: a new upward arrow fails its
package's case, and a repaired one fails it too until it leaves the list.
"""

import ast
import functools
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.join(REPO, "tempo_tpu")

LAYERS = [  # lowest first
    ("util", "model", "ops", "native"),
    ("backend", "cache"),
    ("encoding", "traceql"),
    ("metrics_engine", "graph", "parallel", "compiled", "resultcache", "standing"),
    ("db",),
    ("receivers",),
    ("modules", "usagestats", "vulture", "rca"),
    ("api", "app", "cli", "config", "serverless", "jaeger_plugin", "jaeger_query",
     "__main__"),
]
RANK = {pkg: i for i, layer in enumerate(LAYERS) for pkg in layer}

UPWARD = {  # (from, to): the debt, ROADMAP.md C15
    ("encoding", "modules"),        # vrow/block.py takes querier._search_batch
    ("encoding", "standing"),       # vtpu/create.py: step-partial rules
    ("encoding", "parallel"),       # vtpu/compactor.py: parallel/compaction
    ("metrics_engine", "modules"),  # evaluate.py: generator/registry.Exemplar
    ("receivers", "modules"),       # grpc_server.py: distributor.RateLimited
    ("modules", "api"),             # querier.py: api/params' block-request type
    ("ops", "encoding"),            # lightweight formulas, colcache
    ("ops", "traceql"),             # ingest_tail.py: ast_nodes
    ("util", "backend"),            # circuit.py: retryable_error
    ("util", "encoding"),           # backend.describe, pageheat
    ("backend", "encoding"),        # faults.py: CorruptPage
}


def _imports(path: str, package: list[str]):
    """(absolute dotted name, line) of everything `path` imports,
    function-level imports included. `package` is the dotted path of the
    package that holds the module, for resolving relative imports."""
    with open(path) as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from ((a.name, node.lineno) for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            # `from tempo_tpu import native` names a package, not an attribute
            names = [f"{mod}.{a.name}" for a in node.names] if mod == "tempo_tpu" else [mod]
            yield from ((n, node.lineno) for n in names)


@functools.cache
def edges() -> dict:
    """(from, to) -> the `file:line` places, over top-level packages of
    tempo_tpu/ (a top-level module counts as a package of one file)."""
    out: dict = {}
    for root, _, files in os.walk(ROOT):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(root, f)
            parts = os.path.relpath(path, ROOT)[:-3].split(os.sep)
            package = ["tempo_tpu"] + parts[:-1]
            for name, line in _imports(path, package):
                dotted = name.split(".")
                if dotted[0] == "tempo_tpu" and len(dotted) > 1 and dotted[1] != parts[0]:
                    out.setdefault((parts[0], dotted[1]), []).append(
                        f"{os.path.relpath(path, REPO)}:{line}")
    return out


@functools.cache
def packages() -> frozenset:
    """Top-level packages and modules of tempo_tpu/."""
    names = {os.path.splitext(n)[0] for n in os.listdir(ROOT)
             if n.endswith(".py") or os.path.isdir(os.path.join(ROOT, n))}
    return frozenset(names - {"__init__", "__pycache__"})


@pytest.mark.parametrize("pkg", sorted(RANK))
def test_package_points_up_only_where_listed(pkg):
    assert packages() == set(RANK), (
        f"rank every top-level package of tempo_tpu/: {sorted(packages() ^ set(RANK))}")
    up = {(a, b): places for (a, b), places in edges().items()
          if a == pkg and RANK[a] < RANK[b]}
    listed = {e for e in UPWARD if e[0] == pkg}
    new = {e: sorted(up[e]) for e in set(up) - listed}
    assert not new, f"new upward import(s), move the code down instead: {new}"
    gone = listed - set(up)
    assert not gone, f"repaired: take {sorted(gone)} out of UPWARD and ROADMAP.md C15"
