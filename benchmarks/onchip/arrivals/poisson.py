"""Poisson arrivals at ``rate_per_s``: inter-arrival gaps at the stratified
quantiles (i + 0.5) / n of the exponential distribution."""
import numpy as np


def gaps(spec, n):
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / spec["rate_per_s"]
