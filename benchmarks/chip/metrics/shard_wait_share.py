"""Share of the inference agent's ``stream_round`` time spent in its
``shard_wait`` spans: the wait on S_comp for the next layer's shard, and
nothing else.  From the program's telemetry spans (main thread)."""
from chipbench import spans


def read(run):
    return spans.share_of_rounds(run.spans, "shard_wait")
