"""The arrow between the library and the scripts points one way:
``tools/``, ``benchmark/``, ``chip_smoke.py``, ``tests/`` and
``__graft_entry__.py`` import ``paddle_tpu``; nothing under
``paddle_tpu/`` imports them. One case per top-level package, one for
the top-level modules."""
import ast
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "paddle_tpu")
SCRIPTS = {"tools", "benchmark", "bench", "chip_smoke", "tests",
           "__graft_entry__"}
PACKAGES = sorted(d for d in os.listdir(PKG)
                  if os.path.isfile(os.path.join(PKG, d, "__init__.py")))


def _files(unit):
    if unit == "<modules>":
        return [os.path.join(PKG, f) for f in sorted(os.listdir(PKG))
                if f.endswith(".py")]
    return [os.path.join(root, f)
            for root, _, names in os.walk(os.path.join(PKG, unit))
            for f in sorted(names) if f.endswith(".py")]


def _script_imports(path):
    """(line, module) of every absolute import of a script in ``path``."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [node.module]
        else:
            continue
        for name in names:
            if name.split(".")[0] in SCRIPTS:
                yield node.lineno, name


@pytest.mark.parametrize("unit", PACKAGES + ["<modules>"])
def test_library_imports_no_script(unit):
    files = _files(unit)
    assert files, f"no .py file under paddle_tpu/{unit}"
    hits = [f"{os.path.relpath(path, os.path.dirname(PKG))}:{line} "
            f"imports {name}"
            for path in files for line, name in _script_imports(path)]
    assert not hits, "\n".join(hits)
