"""Median seconds from the time a request was due to its first token,
over every request due in the window."""
from bench.readers import percentile, ttfts


def read(run):
    return percentile(ttfts(run), 50)
