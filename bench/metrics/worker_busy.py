"""Engine worker occupancy: ``task`` span time over k times the ``round``
span time of the window's rounds, in percent."""


def read(obs):
    rounds = sum(b - a for a, b in obs.round_spans().values())
    if rounds <= 0:
        return None
    return 100.0 * obs.span_seconds("task") / (obs.n_workers * rounds)
