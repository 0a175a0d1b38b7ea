"""Process start to the first timed chunk: imports, building the trace
and the cluster, the fill buckets' compiles (or cache loads) and the
warm-up chunks with their admissions."""


def read(win):
    return win.setup_s
