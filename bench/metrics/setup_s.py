"""Process start to the window's start: initialisation, weights, pool,
warm-up and the fill of every slot."""


def read(run):
    return run.setup_s
