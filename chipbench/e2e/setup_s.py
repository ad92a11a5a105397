"""Set-up seconds: from process start to the window: data made on the
device from the seed, programs compiled or loaded from the cache, warm-up."""


def read(run):
    return run.setup_s
