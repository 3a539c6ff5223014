"""Every module of the package, and every suite ``benchmarks.run`` names,
imports: a dangling import in a module no other test loads (a launcher,
a CLI helper) fails here."""
import importlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def _modules():
    """Dotted names of every ``.py`` under ``src/repro`` (namespace
    packages included), ``__main__`` modules left out."""
    for root, _, files in os.walk(os.path.join(SRC, "repro")):
        for f in sorted(files):
            if not f.endswith(".py") or f == "__main__.py":
                continue
            rel = os.path.relpath(os.path.join(root, f), SRC)[:-3]
            parts = rel.split(os.sep)
            if parts[-1] == "__init__":
                parts.pop()
            yield ".".join(parts)


def test_every_module_imports():
    names = sorted(_modules())
    assert len(names) > 70
    for name in names:
        importlib.import_module(name)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    run = importlib.import_module("benchmarks.run")
    for suite, fn in run._ALL.items():
        assert callable(importlib.import_module(fn.__module__).run), suite
