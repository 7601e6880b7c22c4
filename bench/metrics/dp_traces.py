"""Data-plane kernels traced by JAX inside the window: the number of the
program's ``jit.trace`` instants in the window's rounds, 0 once set-up has
traced every shape. Read where the program records its ``plan`` spans,
which came with these instants."""


def read(obs):
    if not obs.n_rounds or all(c != "plan" for c, *_ in obs.spans):
        return None
    return float(sum(1 for c, *_ in obs.spans if c == "jit.trace"))
