"""Operator seconds per round: the program's ``compute`` spans less the
``io.read`` spans nested in them (a join reads its old sides from inside
its compute span), summed over the engine's workers, over the window's
rounds."""


def read(obs):
    if not obs.n_rounds or not obs.spans:
        return None
    return obs.self_seconds("compute", "io.read") / obs.n_rounds
