"""95th percentile over every request due in the window of its time to first
token, from when it was due. A request with no first token by the window's
close enters at (close - due)."""
import numpy as np


def ttfts(record):
    close = record["close"]
    return [(r.first if r.first is not None and r.first <= close else close)
            - r.due for r in record["due"]]


def value(run, record):
    t = ttfts(record)
    return float(np.percentile(t, 95)) * 1e3 if t else None
