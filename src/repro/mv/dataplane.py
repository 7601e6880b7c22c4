"""Array-level data plane for the MV operator hot path (DESIGN.md §9).

Once S/C short-circuits storage I/O, per-round wall time is dominated by the
CPU operator inner loops in ``tableops.py``/``partition.py``. This module
ports those loops to jitted JAX with a Pallas path, behind the same
``impl=`` dispatch idiom as ``kernels/ops.py``:

* ``numpy``     — the bitwise REFERENCE: exactly the vectorized host code
                  the operators always ran, and the data plane off a TPU.
                  The entire existing scenario/partition/incremental bitwise
                  matrix executes on this path unchanged.
* ``xla``       — jitted JAX for the arithmetic passes (splitmix64 hash,
                  the map's multiply, fixed-point encode, sorted-probe);
                  host numpy for permutations, segment sums
                  and what is host-only below. XLA:CPU's sorts and scatters
                  are serial — ``jnp.argsort`` loses to numpy's radix sort
                  by ~10x at 1e7 rows — so sorting stays on host where the
                  operators' bitwise contract permits any stable order.
                  The data plane on a TPU. ``"jax"`` is accepted as an alias.
* ``pallas``    — Pallas kernels for the element-wise passes (hash +
                  fused partition histogram, the map's multiply,
                  fixed-point encode) and a vectorized
                  binary-search probe kernel. The TPU v5e compiler refuses
                  the 64-bit ones (ROADMAP Speed 2); nothing falls back.
* ``interpret`` — the Pallas kernels under the interpreter (CPU correctness
                  validation; what the parity tests exercise).

Resolution order: explicit ``impl=`` argument > ``SC_DATAPLANE`` env (read
ONCE at import; override at runtime with ``set_impl``/``use_impl``) > the
shared ``kernels.dispatch`` configured impl (``REPRO_KERNEL_IMPL``, so the
two dispatch layers agree) > the platform: ``xla`` when JAX's default
backend is a TPU, ``numpy`` otherwise.

Host-only on every platform and for every impl — where XLA on a TPU v5e
does not reproduce numpy bit for bit, or compiles too slowly to use
(measured; DESIGN.md §9):

* the map's softsign stage ``b / (1 + |b|)``: XLA:TPU's float32 division
  is not correctly rounded (a third of 1e7 normal draws differ in the last
  bit), so only the multiply ``a * 1.0001f`` runs on the device;
* the filter compare: XLA flushes float32 subnormals to zero, on the CPU
  and on the TPU, so ``x > 0`` is false for a positive subnormal that
  numpy keeps (and on the chip the device compare took 33x the host's
  time, transfers included);
* the map's multiply and fixed-point encode of a column that is not
  float32: XLA:TPU emulates float64 (its float64 encode differed from
  numpy in 338,239 of 1e7 rows);
* the map's multiply of a float32 column that holds a subnormal value
  (the flush again): the jitted multiply returns a flag with its product,
  and a flagged column is multiplied on the host. Encode needs no such
  rule: ``rint(v * 2^16)`` of a subnormal is 0 with or without the flush;
* ``group_reduce``'s int64 prefix sum: bitwise-equal on the chip, but the
  v5e compiler takes up to a minute per length bucket for the emulated
  64-bit scan.

Parity contract — every primitive is bitwise-equal across impls:

* the map's multiply and add are separate operations (the add runs on the
  host): XLA:CPU contracts ``a*c + f(b)`` into an FMA inside one fused
  computation, which changes the low bit vs numpy's unfused mul-then-add;
* filter compares are pinned to the column's own dtype (f32 column → f32
  threshold, f64 → f64, ints compare against f64), so the mask does not
  move with numpy's promotion rules;
* AGG sums are int64 fixed-point: int64 addition wraps mod 2^64 identically
  in ``np.add.at`` and host ``cumsum``-diff, so segment sums over ANY row
  order inside a group are bitwise-equal — which is what lets the jax path
  use an unstable host sort for grouping;
* the jitted kernels take inputs zero-padded to the next power of two
  (``_pow2_padded``): one compile per size bucket, not per delta or
  partition length; every kernel is element-wise in its padded input, so
  padding never reaches a real row;
* the probe pads its sorted-unique array to the next power of two with
  int64-max sentinels; the hit test gathers at the real-length-clipped
  position, which reproduces the numpy clip semantics even when the probe
  value equals the sentinel.

Non-numpy impls require JAX x64 (int64/uint64 table columns); it is
enabled lazily, per jitted call, through the exception-safe ``_lazy_x64``
scope: on success the setting stays enabled (lazy), but a kernel that raises
restores the prior state — an ``SC_DATAPLANE`` impl switch whose first call
fails cannot leak x64 into the f32-default model stack. ``use_impl``
restores both the impl and the prior x64 setting on exit.

Tracing (``obs.trace``): on the ``xla`` path each device call is one
``dp.<kernel>`` span — padding, the jitted call and the copy back — whose
``nbytes`` is the bytes sent plus the bytes returned, and each jitted entry
records a ``jit.trace`` instant when JAX traces it. The numpy path records
nothing; with tracing off a call site costs one predicate.
"""
from __future__ import annotations

import contextlib
import os
from functools import lru_cache

import numpy as np

from ..kernels import dispatch as _dispatch
from ..obs import trace as obs_trace

__all__ = [
    "configured_impl",
    "set_impl",
    "use_impl",
    "resolve_impl",
    "platform",
    "hash64",
    "partition_ids",
    "partition_index",
    "filter_mask",
    "map_derived",
    "fixed_point_encode",
    "group_reduce",
    "first_occurrence",
    "probe_sorted",
    "AGG_QUANTUM",
]

# Fixed-point quantum for AGG sums (mirrors tableops.AGG_QUANTUM; defined
# here too so the encode kernels don't import the table layer).
AGG_QUANTUM = 2.0**16

_SPLITMIX_C1 = 0xBF58476D1CE4E5B9
_SPLITMIX_C2 = 0x94D049BB133111EB

_I64MAX = np.iinfo(np.int64).max

_VALID = ("numpy", "xla", "pallas", "interpret")
_ALIASES = {"jax": "xla", "jit": "xla"}


def _normalize(impl: str) -> str:
    impl = _ALIASES.get(impl.strip().lower(), impl.strip().lower())
    if impl not in _VALID + ("auto",):
        raise ValueError(
            f"unknown dataplane impl {impl!r}; expected one of "
            f"{_VALID + ('auto',)} (alias 'jax' → 'xla')"
        )
    return impl


def _read_env() -> str:
    env = os.environ.get("SC_DATAPLANE", "")
    return _normalize(env) if env else "auto"


_configured: str = _read_env()


def configured_impl() -> str:
    """The configured data-plane impl ("auto" defers to kernels.dispatch,
    then the platform). Environment is read once at import."""
    return _configured


def set_impl(impl: str | None) -> str:
    """Override the configured impl; ``None`` re-reads ``SC_DATAPLANE``.
    Returns the previous value."""
    global _configured
    prev = _configured
    _configured = _read_env() if impl is None else _normalize(impl)
    return prev


@lru_cache(maxsize=1)
def platform() -> str:
    """JAX's default backend ("cpu", "tpu", ...), queried once per process."""
    import jax

    return jax.default_backend()


def resolve_impl(impl: str = "auto") -> str:
    """Resolve a per-call ``impl`` argument to a concrete implementation.
    With nothing configured the platform decides: the jitted ``xla`` path
    on a TPU, the numpy reference everywhere else."""
    impl = _normalize(impl)
    if impl != "auto":
        return impl
    if _configured != "auto":
        return _configured
    # defer to the shared kernel dispatch so REPRO_KERNEL_IMPL moves both
    # layers; its own "auto" means "nothing configured"
    shared = _dispatch.kernel_impl()
    if shared != "auto":
        return shared
    return "xla" if platform() == "tpu" else "numpy"


@contextlib.contextmanager
def use_impl(impl: str):
    """Scoped impl override: sets the configured impl and restores both the
    impl and the prior JAX x64 setting on exit (normal or exceptional) — so
    a jax-path test leaves the f32-default model tests alone."""
    import jax

    prev_x64 = bool(jax.config.jax_enable_x64)
    prev = set_impl(impl)
    try:
        yield
    finally:
        set_impl(prev)
        jax.config.update("jax_enable_x64", prev_x64)


@contextlib.contextmanager
def _lazy_x64():
    """Lazy, exception-safe x64 enable around one jitted-path call.

    Table columns are int64/uint64/float64, so every non-numpy kernel needs
    ``jax_enable_x64``. It is enabled on entry and deliberately left enabled
    on success (lazy: later calls pay nothing) — but if the kernel raises,
    the prior setting is restored before the error propagates, so switching
    ``SC_DATAPLANE`` to a broken impl cannot leak x64 state into unrelated
    f32 model code.
    """
    import jax

    prev = bool(jax.config.jax_enable_x64)
    if not prev:
        jax.config.update("jax_enable_x64", True)
    try:
        yield
    except BaseException:
        if not prev:
            jax.config.update("jax_enable_x64", False)
        raise


def _pow2_pad(n: int) -> int:
    """Next power of two ≥ n (≥ 8): one jit trace per size bucket instead of
    one per distinct length."""
    p = 8
    while p < n:
        p <<= 1
    return p


def _pow2_padded(*arrays: np.ndarray) -> tuple[int, tuple[np.ndarray, ...]]:
    """Zero-pad same-length 1-D arrays to ``_pow2_pad`` of their length, so
    a jitted kernel compiles once per size bucket rather than once per
    distinct delta or partition length. Returns the real length, to slice
    results back with ``[:n]``."""
    n = len(arrays[0])
    L = _pow2_pad(n)
    if L == n:
        return n, arrays
    return n, tuple(np.concatenate([a, np.zeros(L - n, a.dtype)])
                    for a in arrays)


# ---------------------------------------------------------------------------
# Jitted XLA kernels (built lazily: first non-numpy call pays the traces)
# ---------------------------------------------------------------------------

def _nbytes(*arrays) -> float:
    """Bytes of host and device arrays: what a device call sends plus what
    it returns (a ``dp.<kernel>`` span's ``nbytes``)."""
    return float(sum(a.nbytes for a in arrays))


# span category of each kernel's device calls (no string built per call)
_SPAN = {k: f"dp.{k}" for k in ("hash", "pid", "encode", "encode_w")}


def _on_device(kernel: str, cols: tuple, *static) -> np.ndarray:
    """One call of ``_jk()[kernel]`` on same-length ``cols`` zero-padded to
    their size bucket (then ``static``), its output copied back and sliced
    to the real length: one ``dp.<kernel>`` span."""
    with obs_trace.span(_SPAN[kernel], "") as sp:
        n, padded = _pow2_padded(*cols)
        out = _jk()[kernel](*padded, *static)
        host = np.asarray(out)[:n]
        if obs_trace.enabled():
            sp.set(_nbytes(*padded, out))
    return host


@lru_cache(maxsize=None)
def _jk():
    """Namespace of jitted XLA kernels. The map's multiply is jitted alone
    (see module docstring: FMA contraction). Each jitted entry records a
    ``jit.trace`` instant when JAX traces it, which is never in steady
    state; the shared bodies (``_splitmix``, ``_fixed``) record none, so
    one trace counts once."""
    import jax
    import jax.numpy as jnp

    def _splitmix(k):
        x = k.astype(jnp.uint64)
        x = x ^ (x >> np.uint64(30))
        x = x * np.uint64(_SPLITMIX_C1)
        x = x ^ (x >> np.uint64(27))
        x = x * np.uint64(_SPLITMIX_C2)
        return x ^ (x >> np.uint64(31))

    def _fixed(v):
        # float32 only: v * 2^16 is exact in f32 and so is rint of it, which
        # gives numpy's float64 result with no float64 on the device
        return jnp.rint(v * jnp.float32(AGG_QUANTUM)).astype(jnp.int64)

    def _hash(k):
        obs_trace.instant("jit.trace", "dp.hash")
        return _splitmix(k)

    def _pid(k, P):
        obs_trace.instant("jit.trace", "dp.pid")
        return (_splitmix(k) % np.uint64(P)).astype(jnp.int64)

    def _map_mul(a):
        obs_trace.instant("jit.trace", "dp.map_mul")
        # with the flag: does the column hold a float32 subnormal (zero
        # exponent, nonzero mantissa)? XLA flushes those to zero
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        sub = ((bits & 0x7F800000) == 0) & ((bits & 0x007FFFFF) != 0)
        return a * jnp.float32(1.0001), jnp.any(sub)

    def _encode(v):
        obs_trace.instant("jit.trace", "dp.encode")
        return _fixed(v)

    def _encode_w(v, w):
        obs_trace.instant("jit.trace", "dp.encode_w")
        return _fixed(v) * w

    def _probe(uniq_pad, probe, n_real):
        obs_trace.instant("jit.trace", "dp.probe")
        # n_real is TRACED (a value, not a size): making it static would
        # retrace once per distinct unique-key count, defeating the pow2
        # padding's one-trace-per-size-bucket contract (sc-lint's
        # static-arg-retrace rule guards this)
        pos = jnp.searchsorted(uniq_pad, probe).astype(jnp.int64)
        posc = jnp.clip(pos, 0, jnp.int64(n_real) - 1)
        hit = jnp.take(uniq_pad, posc) == probe
        return hit, posc

    ns = {
        "hash": jax.jit(_hash),
        "pid": jax.jit(_pid, static_argnums=1),
        "map_mul": jax.jit(_map_mul),
        "encode": jax.jit(_encode),
        "encode_w": jax.jit(_encode_w),
        "probe": jax.jit(_probe),
    }
    return ns


# ---------------------------------------------------------------------------
# Pallas kernels (interpret=True on CPU; the map's multiply alone, as in
# _jk — the interpreter compiles through XLA and has the same FMA hazard)
# ---------------------------------------------------------------------------

_BLOCK = 2048  # 1-D element-wise block; multiple of the (8,128) f32 tile


@lru_cache(maxsize=None)
def _pk():
    """Pallas kernel builders, keyed by interpret flag at call time."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _ew_call(kernel, out_dtype, *arrays, interpret):
        """Run an element-wise kernel over same-length 1-D arrays, padding
        to a _BLOCK multiple (padding sliced off the result)."""
        n = arrays[0].shape[0]
        if n == 0:
            return np.empty(0, out_dtype)
        pad = (-n) % _BLOCK
        padded = [np.concatenate([a, np.zeros(pad, a.dtype)]) if pad else a
                  for a in arrays]
        np_ = padded[0].shape[0]
        spec = pl.BlockSpec((_BLOCK,), lambda i: (i,))
        out = pl.pallas_call(
            kernel,
            grid=(np_ // _BLOCK,),
            in_specs=[spec] * len(padded),
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((np_,), out_dtype),
            interpret=interpret,
        )(*padded)
        return np.asarray(out)[:n]

    def hash_kernel(k_ref, o_ref):
        x = k_ref[...].astype(jnp.uint64)
        x = x ^ (x >> np.uint64(30))
        x = x * np.uint64(_SPLITMIX_C1)
        x = x ^ (x >> np.uint64(27))
        x = x * np.uint64(_SPLITMIX_C2)
        o_ref[...] = x ^ (x >> np.uint64(31))

    def hash64(keys, interpret):
        return _ew_call(hash_kernel, np.uint64, keys.astype(np.uint64),
                        interpret=interpret)

    def pid_hist(keys, P, interpret):
        """Fused hash + mod + histogram: pid per row AND per-partition
        counts in one kernel pass. The histogram accumulates across the
        (sequential) grid; padded tail rows are masked into a scratch
        bucket ``P`` that is dropped on return."""
        n = keys.shape[0]
        if n == 0:
            return np.zeros(0, np.int64), np.zeros(P, np.int64)
        pad = (-n) % _BLOCK
        k = np.concatenate([keys, np.zeros(pad, keys.dtype)]) if pad else keys
        np_ = k.shape[0]
        nlen = np.asarray([n], np.int64)

        def kernel(n_ref, k_ref, pid_ref, hist_ref):
            i = pl.program_id(0)
            x = k_ref[...].astype(jnp.uint64)
            x = x ^ (x >> np.uint64(30))
            x = x * np.uint64(_SPLITMIX_C1)
            x = x ^ (x >> np.uint64(27))
            x = x * np.uint64(_SPLITMIX_C2)
            x = x ^ (x >> np.uint64(31))
            pid = (x % np.uint64(P)).astype(jnp.int64)
            pid_ref[...] = pid
            rows = i * _BLOCK + jax.lax.iota(jnp.int64, _BLOCK)
            bucket = jnp.where(rows < n_ref[0], pid, P)
            local = jnp.zeros(P + 1, jnp.int64).at[bucket].add(1)

            @pl.when(i == 0)
            def _init():
                hist_ref[...] = jnp.zeros_like(hist_ref)

            hist_ref[...] += local

        pid, hist = pl.pallas_call(
            kernel,
            grid=(np_ // _BLOCK,),
            in_specs=[
                pl.BlockSpec((1,), lambda i: (0,)),
                pl.BlockSpec((_BLOCK,), lambda i: (i,)),
            ],
            out_specs=[
                pl.BlockSpec((_BLOCK,), lambda i: (i,)),
                pl.BlockSpec((P + 1,), lambda i: (0,)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((np_,), np.int64),
                jax.ShapeDtypeStruct((P + 1,), np.int64),
            ],
            interpret=interpret,
        )(nlen, k)
        return np.asarray(pid)[:n], np.asarray(hist)[:P]

    def map_mul_kernel(a_ref, o_ref):
        o_ref[...] = a_ref[...] * jnp.float32(1.0001)

    def map_mul(a, interpret):
        return _ew_call(map_mul_kernel, a.dtype, a, interpret=interpret)

    def encode_kernel(v_ref, o_ref):
        v = v_ref[...].astype(jnp.float64)
        o_ref[...] = jnp.rint(v * AGG_QUANTUM).astype(jnp.int64)

    def encode_w_kernel(v_ref, w_ref, o_ref):
        v = v_ref[...].astype(jnp.float64)
        o_ref[...] = jnp.rint(v * AGG_QUANTUM).astype(jnp.int64) * w_ref[...]

    def encode(v, w, interpret):
        if w is None:
            return _ew_call(encode_kernel, np.int64, v, interpret=interpret)
        return _ew_call(encode_w_kernel, np.int64, v, w.astype(np.int64),
                        interpret=interpret)

    def probe(uniq_pad, probe_vals, n_real, interpret):
        """Vectorized binary search (searchsorted-left) over the whole
        padded sorted-unique array held in one block; probes stream through
        the grid. Matches the XLA/_probe semantics bitwise."""
        L = uniq_pad.shape[0]
        steps = max(int(L).bit_length(), 1)
        n = probe_vals.shape[0]
        pad = (-n) % _BLOCK
        pv = np.concatenate([probe_vals, np.zeros(pad, probe_vals.dtype)]) \
            if pad else probe_vals
        np_ = pv.shape[0]

        def kernel(u_ref, p_ref, hit_ref, pos_ref):
            u = u_ref[...]
            p = p_ref[...]
            lo = jnp.zeros(p.shape, jnp.int64)
            hi = jnp.full(p.shape, L, jnp.int64)
            for _ in range(steps):
                mid = (lo + hi) >> 1
                below = jnp.take(u, mid) < p
                lo = jnp.where(below, mid + 1, lo)
                hi = jnp.where(below, hi, mid)
            posc = jnp.clip(lo, 0, n_real - 1)
            hit_ref[...] = jnp.take(u, posc) == p
            pos_ref[...] = posc

        spec = pl.BlockSpec((_BLOCK,), lambda i: (i,))
        hit, pos = pl.pallas_call(
            kernel,
            grid=(np_ // _BLOCK,),
            in_specs=[pl.BlockSpec((L,), lambda i: (0,)), spec],
            out_specs=[spec, spec],
            out_shape=[
                jax.ShapeDtypeStruct((np_,), np.bool_),
                jax.ShapeDtypeStruct((np_,), np.int64),
            ],
            interpret=interpret,
        )(uniq_pad, pv)
        return np.asarray(hit)[:n], np.asarray(pos)[:n]

    return {
        "hash64": hash64,
        "pid_hist": pid_hist,
        "map_mul": map_mul,
        "encode": encode,
        "probe": probe,
    }


# ---------------------------------------------------------------------------
# splitmix64 hash / partitioning
# ---------------------------------------------------------------------------

def _hash64_np(keys: np.ndarray) -> np.ndarray:
    x = np.asarray(keys).astype(np.uint64, copy=True)
    with np.errstate(over="ignore"):
        x ^= x >> np.uint64(30)
        x *= np.uint64(_SPLITMIX_C1)
        x ^= x >> np.uint64(27)
        x *= np.uint64(_SPLITMIX_C2)
        x ^= x >> np.uint64(31)
    return x


def hash64(keys: np.ndarray, impl: str = "auto") -> np.ndarray:
    """splitmix64 finalizer — deterministic across runs, platforms, impls."""
    impl = resolve_impl(impl)
    keys = np.asarray(keys)
    if impl == "numpy" or keys.size == 0:
        return _hash64_np(keys)
    with _lazy_x64():
        if impl == "xla":
            # no host-side cast: the kernel's own astype fuses into the jit,
            # saving a full 16B/row round trip over the host arrays
            return _on_device("hash", (keys,))
        return _pk()["hash64"](keys, interpret=impl == "interpret")


def partition_ids(keys: np.ndarray, n_partitions: int,
                  impl: str = "auto") -> np.ndarray:
    """Partition id of each key: ``splitmix64(key) % P`` (0 when P=1)."""
    P = max(int(n_partitions), 1)
    keys = np.asarray(keys)
    if P == 1:
        return np.zeros(len(keys), np.int64)
    impl = resolve_impl(impl)
    if impl == "numpy" or keys.size == 0:
        return (_hash64_np(keys) % np.uint64(P)).astype(np.int64)
    with _lazy_x64():
        if impl == "xla":
            return _on_device("pid", (keys,), P)
        pid, _ = _pk()["pid_hist"](keys, P, interpret=impl == "interpret")
        return pid


def _group_order(pid: np.ndarray, P: int) -> np.ndarray:
    """Stable permutation grouping rows by pid ascending. numpy's stable
    argsort is a radix sort only for ≤16-bit integer keys (~5x faster than
    the int64 path at 1e7 rows), so cast when P fits."""
    if P <= (1 << 16):
        return np.argsort(pid.astype(np.uint16), kind="stable")
    return np.argsort(pid, kind="stable")


def partition_index(keys: np.ndarray, n_partitions: int,
                    impl: str = "auto") -> tuple[np.ndarray, np.ndarray]:
    """Grouped row index of a P-way hash split: ``(order, counts)`` where
    ``order`` permutes rows into partition-major, row-stable order and
    ``counts[p]`` is partition p's row count — so partition p's rows are
    ``order[offset[p] : offset[p] + counts[p]]`` with ``offset = cumsum``.
    Identical across impls (the permutation is fully determined by the
    stable grouping contract)."""
    P = max(int(n_partitions), 1)
    keys = np.asarray(keys)
    n = len(keys)
    if P == 1:
        return np.arange(n, dtype=np.int64), np.asarray([n], np.int64)
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret") and n:
        with _lazy_x64():
            pid, counts = _pk()["pid_hist"](keys, P,
                                            interpret=impl == "interpret")
        return _group_order(pid, P).astype(np.int64, copy=False), counts
    pid = partition_ids(keys, P, impl)
    counts = np.bincount(pid, minlength=P).astype(np.int64)
    return _group_order(pid, P).astype(np.int64, copy=False), counts


# ---------------------------------------------------------------------------
# Element-wise operators: filter compare, map expression
# ---------------------------------------------------------------------------

def _pin_threshold(col: np.ndarray, threshold: float):
    """Compare dtype contract: float columns compare in their own width,
    everything else against float64 (independent of numpy's promotion
    rules)."""
    if col.dtype.kind == "f":
        return col.dtype.type(threshold)
    return np.float64(threshold)


def filter_mask(col: np.ndarray, threshold: float) -> np.ndarray:
    """Boolean FILTER mask ``col > threshold`` under the pinned-dtype
    compare contract. Host-only in every impl (module docstring)."""
    col = np.asarray(col)
    return col > _pin_threshold(col, threshold)


def _has_subnormal(a: np.ndarray) -> bool:
    """Whether a float32 array holds a subnormal value (zero exponent,
    nonzero mantissa), which XLA would flush to zero."""
    bits = a.view(np.uint32)
    return bool(np.any(((bits & 0x7F800000) == 0)
                       & ((bits & 0x007FFFFF) != 0)))


def map_derived(a: np.ndarray, b: np.ndarray | None,
                impl: str = "auto") -> np.ndarray:
    """The MAP expression: ``a*1.0001f + softsign(b)`` (or ``softsign(a)``
    when only one input column exists). Evaluated unfused in every impl —
    each mul/add/div/abs correctly rounded — so the result is bitwise
    independent of batch shape (load-bearing for delta refresh: chunked and
    whole-table evaluation must agree). Only the multiply of a float32
    column with no subnormal value runs on the device; the rest is
    host-only (module docstring)."""
    a = np.asarray(a)
    b = None if b is None else np.asarray(b)
    impl = resolve_impl(impl)
    if b is None:
        return a / (np.float32(1.0) + np.abs(a))
    part = None
    if impl != "numpy" and a.size and a.dtype == np.float32:
        with _lazy_x64():
            if impl == "xla":
                with obs_trace.span("dp.map_mul", "") as sp:
                    n, (x,) = _pow2_padded(a)
                    prod, subnormal = _jk()["map_mul"](x)
                    if not subnormal:
                        part = np.asarray(prod)[:n]
                    if obs_trace.enabled():
                        sp.set(_nbytes(x, subnormal)
                               + (0.0 if part is None else prod.nbytes))
            elif not _has_subnormal(a):
                part = _pk()["map_mul"](a, interpret=impl == "interpret")
    if part is None:  # the reference, and every host-only case
        part = a * np.float32(1.0001)
    return part + b / (np.float32(1.0) + np.abs(b))


# ---------------------------------------------------------------------------
# Fixed-point AGG: encode + weighted segment reduction
# ---------------------------------------------------------------------------

def fixed_point_encode(values: np.ndarray, weights: np.ndarray | None = None,
                       impl: str = "auto") -> np.ndarray:
    """Per-row int64 AGG contribution: ``rint(v * AGG_QUANTUM)`` (times the
    signed Z-set weight when given). Exact: every later addition is integer.
    Only float32 values encode on the device (module docstring: host-only)."""
    values = np.asarray(values)
    impl = resolve_impl(impl)
    if impl == "numpy" or values.size == 0 or values.dtype != np.float32:
        fp = np.rint(np.asarray(values, np.float64) * AGG_QUANTUM).astype(
            np.int64
        )
        return fp if weights is None else fp * weights
    with _lazy_x64():
        if impl == "xla":
            if weights is None:
                return _on_device("encode", (values,))
            return _on_device("encode_w",
                              (values, np.asarray(weights, np.int64)))
        return _pk()["encode"](values, weights, interpret=impl == "interpret")


def _segment_sums_np(contrib_sorted: np.ndarray,
                     ends: np.ndarray) -> np.ndarray:
    """Exact int64 per-segment sums from a sorted contribution vector via
    cumsum-diff; int64 wraparound matches np.add.at bit for bit."""
    with np.errstate(over="ignore"):
        c = np.cumsum(contrib_sorted)
        seg = c[ends].copy()
        seg[1:] -= c[ends[:-1]]
    return seg


def group_reduce(
    keys: np.ndarray,
    cols: dict[str, tuple[np.ndarray, str]],
    weights: np.ndarray | None = None,
    impl: str = "auto",
    stable: bool = False,
) -> tuple[np.ndarray, dict[str, np.ndarray], np.ndarray]:
    """Weighted segment reduction over (implicitly sorted) group keys.

    ``cols`` maps output name → ``(values, kind)``; kind ``"fixed"`` encodes
    values through ``fixed_point_encode`` (times ``weights`` when given),
    kind ``"int"`` sums raw int64 (the AGG ``count`` column of a merge).
    Returns ``(sorted unique keys, {name: int64 sums}, counts)`` with
    ``counts`` the per-group sum of ``weights`` (group sizes when None).

    ``stable`` is the caller's declared order sensitivity: the jitted path
    groups rows with a host sort, and a caller whose per-group accumulation
    is NOT exactly associative (anything but integer sums) MUST pass
    ``stable=True`` to pin the within-group row order. ``op_agg`` /
    ``merge_agg`` accumulate exact int64 fixed-point sums (mod 2^64 addition
    commutes), so they keep the default unstable sort — the deliberately-
    unstable perf path carried as the one ``unstable-sort`` baseline entry
    in ``tools/sc_lint_baseline.json``.

    numpy impl is the reference ``np.unique``+``np.add.at`` loop; the
    jax/pallas impls encode through the device kernels and sum segments by
    a host sort and cumsum (the int64 scan is host-only, module docstring).
    Bitwise-equal because the sums are exact integers (mod 2^64) —
    independent of both accumulation order and grouping method.
    """
    keys = np.asarray(keys)
    impl = resolve_impl(impl)
    if impl == "numpy" or keys.size == 0:
        uniq, inv = np.unique(keys, return_inverse=True)
        n = len(uniq)
        sums: dict[str, np.ndarray] = {}
        with np.errstate(over="ignore"):
            for name, (v, kind) in cols.items():
                contrib = (
                    np.asarray(v, np.int64)
                    if kind == "int"
                    else fixed_point_encode(v, weights, impl="numpy")
                )
                acc = np.zeros(n, np.int64)
                np.add.at(acc, inv, contrib)
                sums[name] = acc
            if weights is None:
                counts = np.bincount(inv, minlength=n).astype(np.int64)
            else:
                counts = np.zeros(n, np.int64)
                np.add.at(counts, inv, weights)
        return uniq, sums, counts
    # jitted path: host sort for the grouping permutation (unstable by
    # default — integer sums commute exactly; see ``stable`` above), device
    # encode, host cumsum-diff for the sums
    if stable:
        order = np.argsort(keys, kind="stable")
    else:
        order = np.argsort(keys)
    sk = keys[order]
    boundary = np.nonzero(sk[1:] != sk[:-1])[0]
    ends = np.concatenate([boundary, [len(sk) - 1]])
    uniq = sk[ends]
    sums = {}
    for name, (v, kind) in cols.items():
        contrib = (
            np.asarray(v, np.int64)
            if kind == "int"
            else fixed_point_encode(v, weights, impl=impl)
        )
        sums[name] = _segment_sums_np(contrib[order], ends)
    if weights is None:
        starts = np.concatenate([[0], ends[:-1] + 1])
        counts = (ends - starts + 1).astype(np.int64)
    else:
        w = np.asarray(weights, np.int64)
        counts = _segment_sums_np(w[order], ends)
    return uniq, sums, counts


# ---------------------------------------------------------------------------
# Join probe: first-occurrence index build + sorted probe
# ---------------------------------------------------------------------------

def first_occurrence(keys: np.ndarray,
                     impl: str = "auto") -> tuple[np.ndarray, np.ndarray]:
    """(sorted unique keys, row index of each key's FIRST occurrence) — the
    PK-style probe index every right join side is reduced to. The stable
    sort is the contract (first occurrence in input order); it runs on host
    in every impl."""
    keys = np.asarray(keys)
    impl = resolve_impl(impl)
    if impl == "numpy" or keys.size == 0:
        order = np.argsort(keys, kind="stable")
        uniq, first = np.unique(keys[order], return_index=True)
        return uniq, order[first]
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    firstmask = np.empty(len(sk), bool)
    firstmask[0] = True
    np.not_equal(sk[1:], sk[:-1], out=firstmask[1:])
    sel = np.nonzero(firstmask)[0]
    return sk[sel], order[sel]


def _sentinel_padded(uniq: np.ndarray) -> np.ndarray:
    """The probe index padded to a power of two with int64-max sentinels:
    one trace per size bucket. Sentinels sort after every real key, so
    positions for probe < I64MAX are unchanged; the hit test gathers at the
    real-clipped position, reproducing numpy clip semantics even for
    probe == I64MAX."""
    L = _pow2_pad(len(uniq))
    if L == len(uniq):
        return uniq
    return np.concatenate([uniq, np.full(L - len(uniq), _I64MAX, uniq.dtype)])


def probe_sorted(uniq: np.ndarray, probe: np.ndarray,
                 impl: str = "auto") -> tuple[np.ndarray, np.ndarray]:
    """Probe sorted-unique ``uniq`` with ``probe`` values: ``(hit, pos)``
    where ``pos`` is the searchsorted-left position clipped to the valid
    range and ``hit[i]`` iff ``uniq[pos[i]] == probe[i]`` — exactly the
    numpy idiom ``op_join`` / ``_right_mapping_changes`` always used.
    Empty ``uniq`` → all-miss with zero positions."""
    uniq = np.asarray(uniq)
    probe = np.asarray(probe)
    if len(uniq) == 0 or len(probe) == 0:
        return np.zeros(len(probe), bool), np.zeros(len(probe), np.int64)
    impl = resolve_impl(impl)
    if impl == "numpy":
        pos = np.searchsorted(uniq, probe)
        posc = np.clip(pos, 0, len(uniq) - 1)
        return uniq[posc] == probe, posc
    with _lazy_x64():
        if impl == "xla":
            with obs_trace.span("dp.probe", "") as sp:
                uniq_pad = _sentinel_padded(uniq)
                n, (pv,) = _pow2_padded(probe)
                hit, pos = _jk()["probe"](uniq_pad, pv, len(uniq))
                out = np.asarray(hit)[:n], np.asarray(pos)[:n]
                if obs_trace.enabled():
                    sp.set(_nbytes(uniq_pad, pv, hit, pos))
            return out
        hit, pos = _pk()["probe"](_sentinel_padded(uniq), probe, len(uniq),
                                  interpret=impl == "interpret")
        return hit, pos
