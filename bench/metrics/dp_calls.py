"""Device calls per round of the data plane: the number of the program's
``dp.<kernel>`` spans, over the window's rounds. Read where the program
records its ``plan`` spans, which came with the ``dp.*`` ones: there no
``dp.*`` span reads 0 (the numpy data plane)."""


def read(obs):
    if not obs.n_rounds or all(c != "plan" for c, *_ in obs.spans):
        return None
    return sum(1 for c, *_ in obs.spans if c.startswith("dp.")) / obs.n_rounds
