"""The seam of a model, held by reading the source: which way the imports
run between ``fedtpu/ops/``, ``fedtpu/models/`` and the layers above them,
what a model module hands the registry, and where a scope's name is written.
Pure Python (``ast`` and imports that touch no backend): under a second."""

from __future__ import annotations

import ast
import importlib
import os

import pytest

from fedtpu.models import registry
from fedtpu.ops import scopes
from fedtpu.parallel import round as round_mod
from fedtpu.training import task

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL_FILES = sorted(path.rsplit(".", 1)[1] + ".py"
                     for path in registry.LANGUAGE_MODELS.values())
ABOVE_OPS = ("fedtpu.models", "fedtpu.parallel", "fedtpu.training",
             "fedtpu.orchestration")
ABOVE_MODELS = ("fedtpu.parallel", "fedtpu.orchestration")


def _files(package: str) -> list:
    folder = os.path.join(ROOT, "fedtpu", package)
    return sorted(os.path.join(folder, name) for name in os.listdir(folder)
                  if name.endswith(".py"))


def _tree(path: str) -> ast.Module:
    with open(path) as fh:
        return ast.parse(fh.read(), path)


def _imported(path: str) -> set:
    """Every dotted module a file imports, at any depth of its source (an
    import inside a function is an import), with the names it takes from a
    package spelled out: ``from fedtpu.models import xing4`` is
    ``fedtpu.models.xing4``."""
    found = set()
    for node in ast.walk(_tree(path)):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{path}: a relative import"
            found.add(node.module)
            found.update(f"{node.module}.{alias.name}" for alias in node.names)
    return found


def _under(modules: set, packages: tuple) -> list:
    return sorted(m for m in modules
                  if any(m == p or m.startswith(p + ".") for p in packages))


@pytest.mark.parametrize("name", MODEL_FILES)
def test_a_model_imports_no_other_model(name):
    others = tuple(path for path in registry.LANGUAGE_MODELS.values()
                   if not path.endswith("." + name[:-3]))
    imported = _imported(os.path.join(ROOT, "fedtpu", "models", name))
    assert _under(imported, others) == []
    # and what the models share imports none of them either
    shared = _imported(os.path.join(ROOT, "fedtpu", "models", "layers.py"))
    assert _under(shared, tuple(registry.LANGUAGE_MODELS.values())) == []


@pytest.mark.parametrize("package,above", [("ops", ABOVE_OPS),
                                           ("models", ABOVE_MODELS)])
def test_no_layer_imports_a_layer_above_it(package, above):
    for path in _files(package):
        assert _under(_imported(path), above) == [], path


@pytest.mark.parametrize("kind", sorted(registry.LANGUAGE_MODELS))
def test_a_model_module_has_the_one_interface(kind):
    model = importlib.import_module(registry.LANGUAGE_MODELS[kind])
    for name in ("check", "init", "sequence_stats"):
        assert callable(getattr(model, name)), (kind, name)
    assert isinstance(model.PER_ROW, tuple) and "padding" in model.PER_ROW
    # no older name beside the interface's, and no wrapper of its own
    for old in (f"{kind}_init", f"{kind}_sequence_stats", f"{kind}_stats"):
        assert not hasattr(model, old), (kind, old)
    # the table is written once: the task reads the registry's
    assert task.LANGUAGE_MODELS is registry.LANGUAGE_MODELS
    assert tuple(task.LANGUAGE_MODELS) == tuple(registry.LANGUAGE_MODELS)


def _scope_arguments(path: str) -> list:
    """``(line, argument)`` of every call of ``jax.named_scope`` in a file."""
    return [(node.lineno, node.args[0]) for node in ast.walk(_tree(path))
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "named_scope"]


def _string_constants(path: str) -> set:
    return {node.value for node in ast.walk(_tree(path))
            if isinstance(node, ast.Constant) and isinstance(node.value, str)}


def test_every_scope_is_a_name_of_the_one_file_that_writes_it():
    names = {name: value for name, value in vars(scopes).items()
             if name.isupper() and isinstance(value, str)}
    every = set(scopes.LAYERS) | set(scopes.PIECES) | set(scopes.MODULES) | {
        scopes.RECOMPUTE}
    assert set(names.values()) == every and len(names) == len(every)
    opened = 0
    for path in _files("models") + _files("ops"):
        for line, arg in _scope_arguments(path):
            # a constant of ``scopes``, by its name: never a literal
            assert isinstance(arg, ast.Name) and arg.id in names, (path, line)
            opened += 1
    assert opened >= 40
    # and no scope's name is spelled out anywhere else in the program. (The
    # one-word names are parameter keys and layer kinds too, ``"embed"``,
    # ``"experts"``: those strings are data, and no scope is opened with
    # them, which the loop above holds.)
    compound = {name for name in every if "_" in name}
    for folder, _, files in os.walk(os.path.join(ROOT, "fedtpu")):
        for name in files:
            path = os.path.join(folder, name)
            if name.endswith(".py") and path != scopes.__file__:
                assert _string_constants(path) & compound == set(), path
    assert round_mod.LAYERS == scopes.LAYERS + ("server_update",)
    assert round_mod.PIECES == scopes.PIECES + ("sgd_pass",)
    assert round_mod.MODULES == scopes.MODULES
    assert round_mod.RECOMPUTE == scopes.RECOMPUTE
    assert round_mod.LAYER_KERNELS == scopes.LAYER_KERNELS


def test_only_the_registry_reads_a_models_name():
    """A model's module and the kernels under it decide by what the
    configuration states (a list, a flag, a shape), so a second model can
    take a stack over by its fields alone: nothing under ``fedtpu/models/``
    or ``fedtpu/ops/`` but the registry reads ``.kind``."""
    for path in _files("models") + _files("ops"):
        if path == registry.__file__:
            continue
        read = [node.lineno for node in ast.walk(_tree(path))
                if isinstance(node, ast.Attribute) and node.attr == "kind"]
        assert read == [], (path, read)


def test_the_registry_is_a_table():
    """No branch a language model in ``build_model``: the kinds its source
    compares ``cfg.kind`` with are the two classifiers'."""
    compared = set()
    for node in ast.walk(_tree(registry.__file__)):
        if (isinstance(node, ast.Compare)
                and isinstance(node.left, ast.Attribute)
                and node.left.attr == "kind"):
            compared.update(c.value for c in node.comparators
                            if isinstance(c, ast.Constant))
    assert compared == {"mlp", "convnet"}
    with pytest.raises(ValueError, match="unknown model kind"):
        from fedtpu.config import ModelConfig
        registry.build_model(ModelConfig(kind="none"))
