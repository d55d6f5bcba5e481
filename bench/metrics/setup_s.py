"""Process start to the end of warm-up (host clock): weights, engine,
compiles or cache loads, warm-up serving."""


def read(run):
    return run.setup_s
