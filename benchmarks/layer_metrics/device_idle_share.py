"""Share of the traced slice of the window in which no operation ran on the
device: 1 - union of device-operation intervals over the slice
(``harness/trace.py``)."""


def read(record):
    w = (record.get("trace") or {}).get("window")
    if not w or not w.get("window_s"):
        return None
    return 100.0 * (1.0 - w["busy_s"] / w["window_s"])
