"""Share of the inference agent's ``stream_round`` time not covered by
its ``compute`` spans: the time it waits on the loading agents (and on
host work between layers).  From the program's telemetry spans."""


def read(run):
    if not run.spans:
        return None
    rounds = [s for s in run.spans if s[0] == "stream_round"]
    total = sum(e - s for _, _, s, e, _ in rounds)
    if total <= 0:
        return None
    computing = sum(e - s for name, _, s, e, _ in run.spans
                    if name == "compute"
                    and any(r[2] <= s and e <= r[3] for r in rounds))
    return (total - computing) / total * 100.0
