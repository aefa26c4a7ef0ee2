"""Requests admitted per batched prefill call in the window (scheduler)."""


def read(rec):
    w = rec["window"]
    admitted = w["attempted"] - w["refused"] - w["queued_at_end"]
    return admitted / w["prefill_calls"] if w["prefill_calls"] else None
