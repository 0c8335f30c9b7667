"""Process start to the first timed step: imports, data generation, the
program's loaders, model build, seeded weights, and one epoch per shuffle of
the mix's cycle (tracing, compile or cache load)."""


def read(run):
    return run["setup_s"]
