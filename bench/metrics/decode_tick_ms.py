"""Device time of one decode tick program, from the trace's XLA Modules
line: the mean over the ticks wholly inside the traced window (model
runner)."""


def read(rec):
    tick = (rec["trace"] or {}).get("programs", {}).get("tick")
    return tick["s_whole"] / tick["n"] * 1e3 if tick and tick["n"] else None
