"""Device seconds per round of the data plane's jitted programs
(``mv/dataplane.py`` ``_jk()``), matched by their XLA module names in the
profiler trace."""

from bench.devtrace import module_name

KERNELS = ("_hash", "_pid", "_map_mul", "_encode", "_encode_w", "_probe")
MODULES = {f"jit_{k}" for k in KERNELS}


def read(obs):
    if not obs.device_modules or not obs.n_rounds:
        return None
    total = sum(d for name, _, d in obs.device_modules
                if module_name(name) in MODULES)
    return total / obs.n_rounds
