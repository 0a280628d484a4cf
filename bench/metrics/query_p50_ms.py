"""Median query latency over every answered query of the window, each timed
from its due time on the open-loop schedule to its resolved ticket."""

import numpy as np


def read(ctx):
    lat = ctx["lat_ms"]
    if ctx["kind"] != "query" or lat is None or not len(lat):
        return None
    return float(np.percentile(lat, 50))
