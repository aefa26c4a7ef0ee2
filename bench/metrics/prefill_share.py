"""Share of the device's busy time spent in the admission programs
(batched prefill and the multi-slot insert), in percent (model runner)."""


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["busy_s"]:
        return None
    p = tr["programs"]
    s = sum(p.get(k, {}).get("s", 0.0) for k in ("prefill", "admit"))
    return 100.0 * s / tr["busy_s"]
