"""Share of the scheduler's ``serve_round`` time outside the
``stream_round`` it holds: page growth, admission, batch and table
build, the head, argmax, the next tokens to the host, retirement.  From
the program's telemetry spans (main thread)."""
from chipbench import spans


def read(run):
    return spans.sched_host_share(run.spans)
