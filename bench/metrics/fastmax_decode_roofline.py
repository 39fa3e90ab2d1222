"""Roofline share of the decode kernel fastmax_decode_p2."""
from bench.readers import roofline


def read(r):
    return roofline(r, "fastmax_decode_p2")
