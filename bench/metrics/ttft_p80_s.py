"""80th percentile of seconds from due to first token, over every request
due in the window (at least ten requests lie beyond it in every cell)."""
from bench.readers import percentile, ttfts


def read(run):
    return percentile(ttfts(run), 80)
