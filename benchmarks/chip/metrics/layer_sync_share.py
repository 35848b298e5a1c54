"""Share of the inference agent's ``stream_round`` time spent in its
``layer_sync`` spans: the ``block_until_ready`` after each layer's work,
which waits for the device and for copies still in flight.  From the
program's telemetry spans (main thread)."""
from chipbench import spans


def read(run):
    return spans.share_of_rounds(run.spans, "layer_sync")
