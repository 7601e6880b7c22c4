"""Seconds per round in the program's ``write.sync`` spans, summed over the
engine's workers, over the window's rounds."""


def read(obs):
    if not obs.n_rounds or not obs.spans:
        return None
    return obs.span_seconds("write.sync") / obs.n_rounds
