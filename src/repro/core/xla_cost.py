"""Access to XLA's ``compiled.cost_analysis()``.

Every consumer in this repo goes through :func:`cost_analysis_dict`, so
the one rule about a missing analysis lives in one place.
"""
from __future__ import annotations


def cost_analysis_dict(compiled) -> dict:
    """Return the entry-computation cost analysis as a plain dict.

    Returns ``{}`` only when the backend has no cost analysis at all
    (JAX then reports ``None``); callers fall back to their own
    estimates in that case.  Any failure of a backend that does have
    one propagates.
    """
    cost = compiled.cost_analysis()
    return {} if cost is None else dict(cost)
