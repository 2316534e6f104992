"""Candidates completed inside the window: what the deadline afforded the
search.  Reported where the rate itself is too unsteady to be held to a
bound (ten candidates of 3.5 or 6.5 s each, PERF.md section 2): the fewer a
window holds, the worse the best it can have found."""


def read(record):
    return record["window"]["n_completed"]
