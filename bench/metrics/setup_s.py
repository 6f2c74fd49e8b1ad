"""Process start to the first timed tick: weights, compression, compile
or cache load, warm-up and the state the mix needs."""


def read(r):
    return r.setup_s
