"""UNION delta seconds per round: the program's ``union.splice`` spans
(``IncrementalEngine``'s regroup of the rids that a UNION's inputs share,
one span per UNION partition it rewrites, old-input reads included),
summed over the engine's workers, over the window's rounds."""


def read(obs):
    if not obs.n_rounds or all(c != "union.splice" for c, *_ in obs.spans):
        return None
    return obs.span_seconds("union.splice") / obs.n_rounds
