"""Traffic source seconds per round: the program's ``ingest.source`` spans,
the scans' own functions (here the benchmark's traffic generator and its
listeners) as the program waits on them, over the window's rounds."""


def read(obs):
    if not obs.n_rounds or all(c != "ingest.source" for c, *_ in obs.spans):
        return None
    return obs.span_seconds("ingest.source") / obs.n_rounds
