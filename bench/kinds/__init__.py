"""What a level or the expert *is*, found by the configuration's ``kind``
string, as per-layer metrics are found by their names.

``<kind>.py`` is the reference side, and imports nothing of the program:

- ``weights(key, spec, n_classes, n_features)``: the weight tree, in the
  program's layout, drawn from one key;
- ``logits(p, x, spec, rnd)``: the plain float32 forward over input rows,
  every matmul operand taken through the rounding ``rnd``;
- ``featurize(spec, doc, n_features)``: one document's input row, and
  ``row(spec, n_features)``: that row's ``(shape, dtype)``;
- ``flops(spec, n_classes, n_features)``: forward FLOPs per item;
- ``OPTIMIZER``: the student's update rule, ``"ogd"`` or ``"adam"``;
- ``balance(params, spec, shift)``, for a kind that can be the expert:
  the weights with the second logit lowered against the first by
  ``shift``;
- ``TINY``: the spec's sizes in the cell's CPU-test copy.

``<kind>_program.py`` is the adapter to the program (with ``system.py``
the only files that import it):

- ``config(spec, n_classes)``: the ``CascadeConfig`` fields a level of
  the kind needs;
- ``build(params, spec, n_classes, cost)``, for an expert kind: the
  program's expert object, and ``PROGRAMS``: the XLA program base names
  of its forward.

A level's ``spec`` is the cascade's entry named by its kind (None if
there is none); the expert's is the expert's own entry.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

DIRS = [Path(__file__).resolve().parent]
_loaded = {}


def _find(name: str) -> Path:
    for d in DIRS:
        path = Path(d) / f"{name}.py"
        if path.is_file():
            return path
    raise LookupError(f"no kind file {name}.py in "
                      + ", ".join(str(d) for d in DIRS))


def _load(name: str):
    path = _find(name)
    if path not in _loaded:
        spec = importlib.util.spec_from_file_location(f"bench_kind_{name}",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def check(kind: str) -> None:
    """Raise ``LookupError`` unless both of the kind's files are there."""
    _find(kind)
    _find(f"{kind}_program")


def reference(kind: str):
    """The kind's reference module, ``<kind>.py``."""
    return _load(kind)


def program(kind: str):
    """The kind's adapter to the program, ``<kind>_program.py``."""
    return _load(f"{kind}_program")
