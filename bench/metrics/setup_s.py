"""Process start to the first hand-off of the window (host clock): JAX's
start, mesh planning, weights, compilation and the warm-up batch."""


def read(r):
    return r.setup_s
