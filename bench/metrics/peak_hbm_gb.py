"""Peak device memory in use, ``memory_stats()["peak_bytes_in_use"]`` read
after the window, in GB."""


def read(rec):
    b = rec["memory"]["peak_bytes"]
    return b / 1e9 if b else None
