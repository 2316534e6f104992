"""Cost of one program's first call (trace, compile, load, first run) in the
window: the executor's ``compile_secs`` over ``compile_count``, both as
differences over the window.  Prefetch workers' seconds are in the sum, so
this is a cost per program, not time the search was blocked."""


def read(record):
    e = record["executor"]
    if not e["first_calls"]:
        return None
    return e["first_call_secs"] / e["first_calls"]
