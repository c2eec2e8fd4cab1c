"""Process start to the first timed round: imports, backend, compile
cache, data from the seed, the reference, warm-up of the cell's own
shape (and, federated, server start and registration)."""


def read(window):
    return window.setup_s
