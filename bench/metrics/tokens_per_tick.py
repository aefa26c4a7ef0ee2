"""Output tokens per decode tick in the window: the mean number of live
slots (scheduler)."""


def read(rec):
    w = rec["window"]
    if not w["decode_calls"]:
        return None
    return sum(r["tokens"] for r in w["requests"]) / w["decode_calls"]
