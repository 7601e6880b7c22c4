"""Backend compiles by time, and the programs they were for.

``CompileCounter`` is a copy of ``chip_smoke.CompileCounter`` that keeps
each compile's ``perf_counter`` time, so the harness can count the
compiles inside the measured window. It also turns on JAX's
``jax_log_compiles`` and reads its "Compiling <function> with global
shapes and types ..." records (kept off standard error), so set-up knows
which jitted functions, at which argument shapes, the build asked for.
"""
from __future__ import annotations

import logging
import re
import time

_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")
_COMPILING = re.compile(r"^Compiling (\S+) with global shapes and types "
                        r"\((.*)\)\. Argument mapping")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


def parse_avals(text: str) -> tuple[tuple[str, tuple[int, ...]], ...]:
    """``((dtype, shape), ...)`` of a logged ``ShapedArray`` list."""
    return tuple((dt, tuple(int(d) for d in dims.split(",") if d))
                 for dt, dims in _ARRAY.findall(text))


class CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__(logging.DEBUG)
        from repro.runtime.jax_private import backend_compile_event

        self._event = backend_compile_event()
        self.times: list[tuple[float, float]] = []  # (when, seconds)
        # (when, function, ((dtype, shape), ...)) of every program traced
        self.programs: list[tuple[float, str, tuple]] = []
        self._saved: list = []

    def __call__(self, event: str, duration: float, **_):
        if event == self._event:
            self.times.append((time.perf_counter(), duration))

    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILING.match(record.getMessage())
        if m:
            self.programs.append((time.perf_counter(), m.group(1),
                                  parse_avals(m.group(2))))

    def between(self, lo: float, hi: float) -> list[float]:
        return [d for t, d in self.times if lo <= t <= hi]

    def programs_between(self, lo: float, hi: float) -> list[tuple]:
        return [(f, a) for t, f, a in self.programs if lo <= t <= hi]

    def __enter__(self):
        import jax
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        self._saved = [bool(jax.config.jax_log_compiles)]
        jax.config.update("jax_log_compiles", True)
        for name in _LOGGERS:
            lg = logging.getLogger(name)
            self._saved.append((lg, lg.propagate))
            lg.propagate = False
            lg.addHandler(self)
        return self

    def __exit__(self, *exc):
        import jax
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self)
        jax.config.update("jax_log_compiles", self._saved[0])
        for lg, propagate in self._saved[1:]:
            lg.removeHandler(self)
            lg.propagate = propagate
