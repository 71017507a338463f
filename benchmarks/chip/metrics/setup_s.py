"""Process start to the first timed request (host clock): netlist load or
build, program load or compile, XLA compile or cache load, the schedule
and the warm-up."""


def read(run):
    return run["setup_s"]
