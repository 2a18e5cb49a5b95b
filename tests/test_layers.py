"""The package graph points one way.

Each layer may import its own layer and the layers below it, never above:

  L0  repro.core, repro.kernels.grin_moves, repro.obs   math, gain kernel,
                                                        spans
  L1  repro.sched                                       policies, SchedulerCore,
                                                        grid solve, governor
  L2  repro.sim, repro.traffic, repro.faults            what-if engines
  L3  repro.launch, repro.serve, repro.models,          serving
      repro.configs, repro.parallel, the LM kernels

Two checks per package: what a fresh interpreter loads when it imports the
package first, and every `repro.*` import in its source, function-local
ones included. A module in no layer fails both.
"""
import ast
import json
import os
import pathlib
import subprocess
import sys

import pytest

import repro

SRC = pathlib.Path(repro.__path__[0])

# longest prefix wins; the package inits of `repro` and `repro.kernels`
# hold no imports, so they sit at the bottom
LAYERS = {
    "repro.core": 0, "repro.kernels": 3,
    "repro.kernels.grin_moves": 0, "repro.obs": 0,
    "repro.sched": 1,
    "repro.sim": 2, "repro.traffic": 2, "repro.faults": 2,
    "repro.launch": 3, "repro.serve": 3, "repro.models": 3,
    "repro.configs": 3, "repro.parallel": 3,
}
PACKAGE_INITS = {"repro", "repro.kernels"}

PACKAGES = ["repro.core", "repro.kernels.grin_moves", "repro.obs",
            "repro.sched", "repro.sim", "repro.traffic", "repro.faults"]


def layer_of(module: str):
    """The layer of a `repro` module, or None if it is in no layer."""
    if module in PACKAGE_INITS:
        return 0
    best = max((p for p in LAYERS
                if module == p or module.startswith(p + ".")),
               key=len, default=None)
    return None if best is None else LAYERS[best]


def _path(name: str) -> pathlib.Path:
    return SRC.joinpath(*name.split(".")[1:])


def _is_package(name: str) -> bool:
    return (_path(name) / "__init__.py").is_file()


def _is_module(name: str) -> bool:
    return _path(name).with_suffix(".py").is_file() or _is_package(name)


def _source_files(pkg: str):
    path = _path(pkg)
    if not _is_package(pkg):
        return {pkg: path.with_suffix(".py")}
    out = {}
    for f in sorted(path.rglob("*.py")):
        parts = f.relative_to(SRC.parent).with_suffix("").parts
        if parts[-1] == "__init__":
            parts = parts[:-1]
        out[".".join(parts)] = f
    return out


def _repro_imports(module: str, source: str):
    """(line, target) of every `repro.*` import in `source`, at any depth."""
    package = module if _is_package(module) else module.rpartition(".")[0]
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] == "repro":
                    yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                anchor = package.split(".")
                anchor = anchor[:len(anchor) - node.level + 1]
                base = ".".join(anchor + ([base] if base else []))
            if base.split(".")[0] != "repro":
                continue
            for alias in node.names:
                sub = f"{base}.{alias.name}"
                yield node.lineno, sub if _is_module(sub) else base


@pytest.mark.parametrize("pkg", PACKAGES)
def test_package_imports_first(pkg):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(SRC.parent)]
                   + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    code = (f"import json, sys\nimport {pkg}\n"
            "print(json.dumps(sorted(m for m in sys.modules\n"
            "                        if m.split('.')[0] == 'repro')))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    loaded = json.loads(out.stdout.strip().splitlines()[-1])
    own = layer_of(pkg)
    assert pkg in loaded
    assert not [m for m in loaded if m.split(".")[:2] == ["repro", "train"]]
    above = [(m, layer_of(m)) for m in loaded
             if layer_of(m) is None or layer_of(m) > own]
    assert not above, f"importing {pkg} (L{own}) loads {above}"


@pytest.mark.parametrize("pkg", PACKAGES)
def test_package_source_imports_point_down(pkg):
    own = layer_of(pkg)
    bad = []
    for module, path in _source_files(pkg).items():
        assert layer_of(module) == own, module
        for line, target in _repro_imports(module, path.read_text()):
            if layer_of(target) is None or layer_of(target) > own:
                bad.append(f"{path.relative_to(SRC.parent)}:{line} imports "
                           f"{target} (L{layer_of(target)})")
    assert not bad, f"{pkg} is L{own}:\n" + "\n".join(bad)
