"""The program's side of ``lr``: its logistic regression takes its width
from ``CascadeConfig.n_features``, so a level of this kind adds no field."""


def config(spec, n_classes: int) -> dict:
    return {}
