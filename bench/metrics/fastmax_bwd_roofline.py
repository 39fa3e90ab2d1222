"""Roofline share of the causal backward kernel fastmax_causal_bwd_p2."""
from bench.readers import roofline


def read(r):
    return roofline(r, "fastmax_causal_bwd_p2")
