"""Uniform lengths over [``min``, ``max``]."""


def quantile(dist: dict, u: float) -> float:
    return dist["min"] + (dist["max"] - dist["min"]) * u
