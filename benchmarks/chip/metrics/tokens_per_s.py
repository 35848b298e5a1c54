"""Output tokens served in the window over the window's length."""


def read(run):
    return run.tokens / run.window_s
