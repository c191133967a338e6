"""The package's layer order, and the registry of its environment names.

A unit of ``photon_ml_tpu/`` may import the units below it in ``ORDER``
and nothing above it: it may know what it is built from, never who uses
it, and nothing outside the package that runs or measures it.

``KNOWN_UPWARD`` is every import that breaks the order today (ROADMAP
D14 groups them by the move that would clear each group).  The list may
only shrink: a new upward import fails its unit's case, and so does an
entry left behind by the change that repaired it.
"""

import ast
import functools
import pathlib

import pytest

from photon_ml_tpu import config

pytestmark = pytest.mark.fast

PACKAGE = pathlib.Path(config.__file__).parent

# Bottom to top.  ``config`` is at the bottom for its env registry,
# which everything reads; ``cache`` for the atomic writes and
# fingerprints that ``reliability``, ``data``, ``io`` and ``serving``
# use; ``analysis`` reads the whole package's source, so it is on top.
ORDER = (
    "config", "utils", "telemetry", "native", "cache", "reliability",
    "ops", "data", "parallel", "optim", "models", "evaluation", "game",
    "io", "hyperparameter", "estimators", "serving", "cli", "analysis",
)

# (importer module, imported unit), module-level and function-local
# imports alike.
KNOWN_UPWARD = {
    # the enums of five packages that the config dataclasses name
    ("photon_ml_tpu.config", "data"),
    ("photon_ml_tpu.config", "evaluation"),
    ("photon_ml_tpu.config", "models"),
    ("photon_ml_tpu.config", "ops"),
    ("photon_ml_tpu.config", "optim"),
    # the run log feeds the live monitor
    ("photon_ml_tpu.utils.run_log", "telemetry"),
    # the status server and the serve report stand on serving's HTTP
    # core and request traces; the monitor reads the fleet's context
    ("photon_ml_tpu.telemetry.monitor", "serving"),
    ("photon_ml_tpu.telemetry.serve_report", "serving"),
    ("photon_ml_tpu.telemetry.monitor", "parallel"),
    ("photon_ml_tpu.native", "ops"),
    # the plan codec knows GrrPair
    ("photon_ml_tpu.cache.plan_cache", "data"),
    # the objective is written against Batch
    ("photon_ml_tpu.ops.objective", "data"),
    # ChunkPrefetcher lives beside the mesh
    ("photon_ml_tpu.data.chunked_batch", "parallel"),
    ("photon_ml_tpu.models.game", "game"),
    ("photon_ml_tpu.evaluation.sharded", "game"),
}

# What runs or measures the package; nothing inside it may know them.
OUTSIDE = {"benchmark", "bench", "examples", "tests"}


def _module_name(path: pathlib.Path) -> str:
    parts = ("photon_ml_tpu",) + path.relative_to(PACKAGE).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


@functools.cache
def _trees() -> dict:
    """module name -> (its file, its syntax tree), the whole package."""
    return {_module_name(p): (p, ast.parse(p.read_text()))
            for p in sorted(PACKAGE.rglob("*.py"))}


def _imported(module: str, path: pathlib.Path, tree: ast.AST):
    """Every dotted name ``module`` imports, absolute."""
    package = module if path.name == "__init__.py" \
        else module.rpartition(".")[0]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package.split(".")
                up = up[:len(up) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            # ``from photon_ml_tpu import telemetry`` names a unit in
            # its alias, not in its module.
            yield from (f"{base}.{alias.name}" for alias in node.names)


def _unit_of(dotted: str) -> str | None:
    parts = dotted.split(".")
    if parts[0] != "photon_ml_tpu" or len(parts) < 2:
        return None
    return parts[1]


@functools.cache
def _edges() -> dict:
    """importer unit -> {(importer module, imported unit or outside
    package)}: what crosses a unit's boundary."""
    edges = {unit: set() for unit in ORDER}
    for module, (path, tree) in _trees().items():
        unit = _unit_of(module)
        for dotted in _imported(module, path, tree):
            top = dotted.partition(".")[0]
            target = top if top in OUTSIDE else _unit_of(dotted)
            if unit is None:
                # the package's own __init__ imports none of its units
                assert target is None, (module, dotted)
            elif target in OUTSIDE or (target in ORDER and target != unit):
                edges[unit].add((module, target))
    return edges


def test_the_order_names_every_unit():
    on_disk = {p.stem if p.is_file() else p.name
               for p in PACKAGE.iterdir()
               if (p.suffix == ".py" and p.stem != "__init__")
               or (p / "__init__.py").is_file()}
    assert set(ORDER) == on_disk and len(set(ORDER)) == len(ORDER)
    assert {_unit_of(m) for m, _ in KNOWN_UPWARD} <= set(ORDER)


@pytest.mark.parametrize("unit", ORDER)
def test_a_unit_imports_only_what_is_below_it(unit):
    edges = _edges()[unit]
    outside = sorted(e for e in edges if e[1] in OUTSIDE)
    assert not outside, f"the package may not know {outside}"
    upward = {(module, target) for module, target in edges
              if ORDER.index(target) > ORDER.index(unit)}
    known = {e for e in KNOWN_UPWARD if _unit_of(e[0]) == unit}
    assert not upward - known, (
        f"new upward imports (move the code down, or the caller up): "
        f"{sorted(upward - known)}")
    assert not known - upward, (
        f"repaired: strike from KNOWN_UPWARD {sorted(known - upward)}")


@functools.cache
def _read_env_names() -> set:
    """Every name some module reads through ``read_env``: the literal,
    or a module-level constant of the package that holds it (as
    ``grr.PLAN_CACHE_ENV`` does)."""
    constants, arguments = {}, []
    for _path, tree in _trees().values():
        for node in tree.body:
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)):
                constants.update({t.id: node.value.value
                                  for t in node.targets
                                  if isinstance(t, ast.Name)})
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and node.args and "read_env" in (
                    getattr(node.func, "id", None),
                    getattr(node.func, "attr", None)):
                arguments.append(node.args[0])
    names = set()
    for arg in arguments:
        if isinstance(arg, ast.Constant):
            names.add(arg.value)
        else:
            names.add(constants.get(getattr(arg, "id", None)
                                    or getattr(arg, "attr", None)))
    return names


@pytest.mark.parametrize("name", sorted(config.SANCTIONED_ENV))
def test_a_registered_env_name_is_read_by_the_package(name):
    """``SANCTIONED_ENV`` calls itself the package's whole environment
    surface: a name nothing in the package reads is not part of it."""
    assert name in _read_env_names()
