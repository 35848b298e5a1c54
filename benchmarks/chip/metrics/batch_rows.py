"""Rows the scheduler decoded per round in the window, as served by
``step()``."""


def read(run):
    return sum(r.decoded for r in run.rounds) / len(run.rounds)
