"""Process start to window open: imports, weights made on the device from the
seed, programs compiled or loaded from the cache, warm-up."""


def value(run, record):
    return run.setup_s
