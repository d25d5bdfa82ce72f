"""Process start to the first due request: weights, engine, compiles."""


def read(run):
    return run.setup_s
