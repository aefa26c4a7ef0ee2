"""Share of the traced window in which no operation ran on the device, in
percent."""


def read(rec):
    tr = rec["trace"]
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
