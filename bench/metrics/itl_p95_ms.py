"""95th percentile of the gap between tokens: a delivery of n tokens that
comes g seconds after that request's previous delivery gives n samples of
g/n; the tokens that arrive with a request's first are gaps of 0."""
import numpy as np


def read(rec):
    g = [x for r in rec["window"]["requests"] for x in r["gaps"]]
    return float(np.percentile(g, 95)) * 1e3 if g else None
