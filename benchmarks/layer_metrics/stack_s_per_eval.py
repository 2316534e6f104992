"""Mean foreground time inside the measurement stack per completed
candidate (verify, wait for the program's first call, measure).  Source:
the wrapper's ``measure`` spans up to the last completion."""


def read(record):
    w = record["window"]
    if not w["n_completed"]:
        return None
    return w["measure_s"] / w["n_completed"]
