"""Steady arrivals: one rate, ``rate_rps``, over the whole run."""


def segments(params, t0: float, t1: float):
    return [(t0, t1, float(params["rate_rps"]))]
