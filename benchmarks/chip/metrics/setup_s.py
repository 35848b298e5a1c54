"""Process start to window start: weights, checkpoint, plan, prefill and
the warm decode round, compiles included."""


def read(run):
    return run.setup_s
