"""Every module of the package imports, and none of them pulls in a
package that was removed from the tree (a dangling import in a module
that no other test imports shows up here)."""

import importlib
import pkgutil
import sys

import repro

GONE = tuple(f"repro.{pkg}" for pkg in ("models", "train", "launch"))


def test_every_module_imports_without_removed_packages():
    for module in pkgutil.walk_packages(repro.__path__, "repro."):
        importlib.import_module(module.name)
    assert not [m for m in sys.modules if m.startswith(GONE)]
