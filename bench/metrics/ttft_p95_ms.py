"""95th percentile of the time from a request's due time to its first token
visible on the host, over every request that was due in the window: one
whose first token had not come when the window closed counts the time to
the close, so a stall at the end raises the tail instead of leaving it."""
import numpy as np


def read(rec):
    w = rec["window"]
    t = [(w["window_s"] if r["first"] is None else r["first"]) - r["due"]
         for r in w["requests"]]
    return float(np.percentile(t, 95)) * 1e3 if t else None
