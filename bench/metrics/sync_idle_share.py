"""Share of the traced window in which the device is idle while the engine
syncs (the part of the idle gaps that the engine's ``serve.sync.wait`` and
``serve.sync.host`` spans cover), in percent (engine host loop)."""


def read(rec):
    tr = rec["trace"]
    idle = (tr or {}).get("idle_by_program_span")
    if idle is None:
        return None
    s = idle.get("serve.sync.wait", 0.0) + idle.get("serve.sync.host", 0.0)
    return 100.0 * s / tr["window_s"]
