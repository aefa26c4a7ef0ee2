"""Output tokens that became visible on the host in the window, per second
of the window."""


def read(rec):
    w = rec["window"]
    return sum(r["tokens"] for r in w["requests"]) / w["window_s"]
