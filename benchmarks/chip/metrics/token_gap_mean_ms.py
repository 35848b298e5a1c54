"""The window's length over the rounds in it: the mean wait between two
tokens of one row, stalls included."""


def read(run):
    return run.window_s / len(run.rounds) * 1e3
