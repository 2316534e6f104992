"""Share of the window the host spent outside the measurement stack: in the
solver (enumeration, neighbour construction, the solver's own verifier
calls).  Window = open to the last completion.  Source: the wrapper's spans."""


def read(record):
    w = record["window"]
    if not w["span_s"]:
        return None
    return 100.0 * (w["span_s"] - w["measure_s"]) / w["span_s"]
