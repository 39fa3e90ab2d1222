"""Roofline share of the causal forward kernel fastmax_causal_p2."""
from bench.readers import roofline


def read(r):
    return roofline(r, "fastmax_causal_p2")
