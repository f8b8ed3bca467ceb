"""The program's side of ``tinytf``: its ``TinyTFSpec`` (``tf_spec`` for a
level), and ``ModelExpert`` over it for the expert."""
from repro.core.experts import ModelExpert
from repro.models.students import TinyTFSpec

# ModelExpert's jitted forward (a lambda) and the argmax over its output
PROGRAMS = frozenset({"_lambda", "_argmax"})


def _spec(spec: dict, n_classes: int) -> TinyTFSpec:
    return TinyTFSpec(**{k: v for k, v in spec.items()
                         if k not in ("kind", "precision")},
                      n_classes=n_classes)


def config(spec: dict, n_classes: int) -> dict:
    return {"tf_spec": _spec(spec, n_classes)}


def build(params, spec: dict, n_classes: int, cost: float) -> ModelExpert:
    return ModelExpert(params=params, spec=_spec(spec, n_classes), cost=cost)
