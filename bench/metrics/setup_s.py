"""Process start to the first request: imports, weights, engine,
warm-up and, in a run that compiles, compilation."""


def read(rec):
    return rec.setup["setup_s"]
