"""Process start to window start: data, engine, warm-up, compiles and
the tuning-cache fill the traffic asks for."""


def read(run):
    return run.setup_s
