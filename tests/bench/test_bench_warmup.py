"""Set-up warms the data-plane programs the build showed, and the compile
counter reads which programs JAX traced."""
import numpy as np

from bench import warmup
from bench.compiles import CompileCounter, parse_avals


def _p(fn, *shapes, dtype="int64"):
    return fn, tuple((dtype, s) for s in shapes)


def test_plan_covers_the_buckets_the_build_reached():
    build = [
        _p("jit(_pid)", (65536,)),
        _p("jit(_map_mul)", (16384,), dtype="float32"),
        _p("jit(_encode)", (4096,), dtype="float32"),
        _p("jit(_probe)", (1024,), (8192,), ()),
        _p("jit(_probe)", (2048,), (16384,), ()),
        _p("jit(_other)", (8,)),
    ]
    plan = warmup.plan(build)
    assert set(plan) == {"jit(_pid)", "jit(_map_mul)", "jit(_encode)",
                         "jit(_encode_w)", "jit(_probe)"}
    assert plan["jit(_pid)"] == set(warmup.buckets(65536))
    assert min(plan["jit(_map_mul)"]) == 8
    assert max(plan["jit(_map_mul)"]) == 16384
    assert plan["jit(_encode_w)"] == plan["jit(_encode)"] \
        == set(warmup.buckets(4096))
    # the probe: only the index sizes the build used, every rows bucket
    assert {k for k, _ in plan["jit(_probe)"]} == {1024, 2048}
    assert len(plan["jit(_probe)"]) == 2 * len(warmup.buckets(16384))
    assert warmup.plan([]) == {}


def test_counter_reads_traced_programs():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: jnp.sum(a) + b)
    with CompileCounter() as cc:
        f(np.ones(8, np.float32), np.float32(1))
        f(np.ones(16, np.float32), np.float32(1))
        f(np.ones(16, np.float32), np.float32(2))  # no new program
    shapes = [a for _, _, a in cc.programs]
    assert shapes == [(("float32", (8,)), ("float32", ())),
                      (("float32", (16,)), ("float32", ()))]
    assert not jax.config.jax_log_compiles
    assert parse_avals("ShapedArray(int64[4,2]), ShapedArray(int64[], "
                       "weak_type=True)") == (("int64", (4, 2)),
                                              ("int64", ()))
