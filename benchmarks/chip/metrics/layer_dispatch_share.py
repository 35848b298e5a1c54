"""Share of the inference agent's ``stream_round`` time spent in its
``layer_dispatch`` spans: Python calling the jitted layer functions,
which return once the work is enqueued.  From the program's telemetry
spans (main thread)."""
from chipbench import spans


def read(run):
    return spans.share_of_rounds(run.spans, "layer_dispatch")
