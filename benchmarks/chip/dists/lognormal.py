"""Lognormal lengths: ``median`` x exp(``sigma`` z)."""
import math
from statistics import NormalDist


def quantile(dist: dict, u: float) -> float:
    return dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
