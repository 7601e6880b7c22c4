"""Store read seconds per round: the program's ``io.read`` spans (every
``DiskStore`` part-file read, throttle stalls included), summed over the
engine's workers, over the window's rounds. ``read.disk`` spans alone would
miss the reads of old join sides, which run inside ``compute`` spans."""


def read(obs):
    if not obs.n_rounds or not obs.spans:
        return None
    return obs.span_seconds("io.read") / obs.n_rounds
