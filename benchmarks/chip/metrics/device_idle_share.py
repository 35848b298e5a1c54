"""Share of the traced window in which the device ran no operation:
1 - (union of device op intervals) / window."""


def read(run):
    if run.trace is None:
        return None
    return (1.0 - run.trace["busy_s"] / run.trace["window_s"]) * 100.0
