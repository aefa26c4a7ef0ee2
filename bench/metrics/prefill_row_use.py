"""Useful share of the positions admission prefill computed: the admitted
prompt tokens over the rows times bucket of every prefill call, padding rows
and columns included, from the engine's counters over the window, in percent
(scheduler)."""


def read(rec):
    c = rec.get("counters")
    if not c or not c.get("prefill_positions"):
        return None
    return 100.0 * c["prefill_tokens"] / c["prefill_positions"]
