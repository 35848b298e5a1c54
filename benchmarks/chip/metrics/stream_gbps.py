"""Shard bytes the loading agents streamed in the window (the program's
``load_end`` events, at manifest sizes), per second of the window."""


def read(run):
    return run.streamed_bytes / run.window_s / 1e9
