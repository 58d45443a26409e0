"""The package order of `risingwave_tpu/`, as a test.

A package may import itself and any package below it:

    common, native < utils < expr < ops < parallel < storage, state
      < connectors < stream < batch < meta < frontend < cluster
      < models, __main__

Every module is parsed with `ast`, function-local imports included.
The upward edges that exist are listed in `UPWARD`, each with the
ROADMAP debt that removes it. A case fails when a module gains an
upward import that is not listed, and when a listed one is gone: take
it out of the list then, so that the list can only shrink.
"""

import ast
import os

import pytest

ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "risingwave_tpu")

LEVELS = (("common", "native"), ("utils",), ("expr",), ("ops",),
          ("parallel",), ("storage", "state"), ("connectors",),
          ("stream",), ("batch",), ("meta",), ("frontend",),
          ("cluster",), ("models", "__main__"))
RANK = {pkg: i for i, level in enumerate(LEVELS) for pkg in level}

# importing module -> {imported module prefix: ROADMAP debt}
UPWARD = {
    "utils.ledger": {"stream.freshness": "D16", "stream.costs": "D16"},
    "parallel.agg": {"stream.costs": "D16"},
    "parallel.join": {"stream.costs": "D16"},
    "parallel.exchange": {"state.topology": "D4"},
    "state.topology": {"stream.costs": "D16"},
    "ops.fused": {"frontend.opt": "D15", "stream": "D15"},
    "stream.executors.hash_agg": {"frontend.opt.fusion": "D15"},
    "stream.executors.hash_join": {"frontend.opt.fusion": "D15"},
    "stream.plan_ir": {"frontend": "D13"},
    "stream.costs": {"meta": "D4"},
    "stream.executors.sink": {"meta": "D8"},
    "meta.autoscaler": {"cluster": "D6"},
}


def _is_module(dotted: str) -> bool:
    path = os.path.join(ROOT, *dotted.split("."))
    return os.path.isdir(path) or os.path.isfile(path + ".py")


def _imports(path: str, module: str):
    """Modules of this package that `module` imports, named from the
    package's root (`stream.costs`)."""
    here = module.split(".")
    if not path.endswith("__init__.py"):
        here = here[:-1]
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = here[:len(here) - (node.level - 1)]
                base = ".".join(["risingwave_tpu"] + up
                                + ([base] if base else []))
            # `from risingwave_tpu.stream import costs` names a
            # module, `from ...stream.costs import COSTS` does not
            names = [base + "." + a.name
                     if _is_module((base + "." + a.name).partition(".")[2])
                     else base for a in node.names]
        else:
            continue
        for name in names:
            if name.startswith("risingwave_tpu."):
                yield name.partition(".")[2]


def _upward_edges(package: str):
    """{module: {imported module}} over the package's modules, for the
    imports that reach a package above it."""
    if package == "__main__":
        files = [(os.path.join(ROOT, "__main__.py"), "__main__")]
    else:
        files = []
        for dirpath, _dirs, names in os.walk(os.path.join(ROOT, package)):
            for name in sorted(names):
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                module = os.path.relpath(path, ROOT)[:-3].replace(
                    os.sep, ".")
                if module.endswith(".__init__"):
                    module = module[:-len(".__init__")]
                files.append((path, module))
    edges = {}
    for path, module in files:
        for target in _imports(path, module):
            top = target.split(".")[0]
            assert top in RANK, (
                f"{module} imports {target}: give the package "
                f"{top!r} a level in LEVELS")
            if RANK[top] > RANK[package]:
                edges.setdefault(module, set()).add(target)
    return edges


def _under(target: str, prefix: str) -> bool:
    return target == prefix or target.startswith(prefix + ".")


def test_every_package_has_a_level():
    on_disk = {name[:-3] if name.endswith(".py") else name
               for name in os.listdir(ROOT)
               if name != "__pycache__" and name != "__init__.py"}
    assert on_disk == set(RANK)
    assert all(module.split(".")[0] in RANK for module in UPWARD)


@pytest.mark.parametrize("package", sorted(RANK, key=RANK.get))
def test_imports_point_down(package):
    edges = _upward_edges(package)
    listed = {module: prefixes for module, prefixes in UPWARD.items()
              if module.split(".")[0] == package}
    new = sorted(
        f"{module} -> {target}"
        for module, targets in edges.items() for target in targets
        if not any(_under(target, p) for p in listed.get(module, ())))
    assert not new, (
        "imports of a package above the importer's own (move the "
        "shared piece down, or let the upper module register with "
        f"the lower one): {new}")
    gone = sorted(
        f"{module} -> {prefix} ({debt})"
        for module, prefixes in listed.items()
        for prefix, debt in prefixes.items()
        if not any(_under(t, prefix) for t in edges.get(module, ())))
    assert not gone, (
        f"no longer imported: take these out of UPWARD: {gone}")
