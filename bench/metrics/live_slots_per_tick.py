"""Occupied slots at each decode tick's dispatch, the mean over the window's
ticks, from the engine's counters (scheduler)."""


def read(rec):
    c = rec.get("counters")
    if not c or not c.get("decode_calls"):
        return None
    return c["live_slot_ticks"] / c["decode_calls"]
