"""TPU runtime telemetry: XLA compile, device memory, and link traffic.

The reference exposes per-subsystem prometheus registries (SURVEY §5);
the TPU-native equivalent must also surface what the ACCELERATOR is
doing — a 25 s XLA recompile or an HBM cache that stopped fitting is
invisible in query latency histograms alone. Three feeds:

- **Compiles**: `jax.monitoring` emits a start scalar and a duration
  event per backend compile (`/jax/core/compile/backend_compile_duration`)
  for every `jax.jit` entry point in ops/ and query/physical.py, on the
  compiling thread — one pair of listeners covers them all without
  wrapping call sites. Each compile is a `compile` span under that
  thread's innermost open span (so it hangs off the request that waited
  for it, or that kicked the warm-up), and is counted by `fn` (a name
  from `kernel_name`, else "eager") and `thread` (request | warmup).
- **Device memory**: a render-time collector reads the PJRT allocator's
  `memory_stats()` (bytes_in_use / bytes_limit on TPU; the CPU backend
  reports none) plus the device block cache's own pinned-bytes
  accounting, which works on every backend.
- **Transfers**: `count_h2d`/`count_d2h` are called at the scan-block
  upload and result-readback seams in query/physical.py and
  query/device_cache.py, and by promql/engine.py's `h2d` / `d2h`.

`install()` is idempotent and cheap; importing query/physical.py wires
everything.
"""

from __future__ import annotations

import functools
import threading
import weakref

from greptimedb_tpu.utils import ledger, tracing
from greptimedb_tpu.utils.metrics import (
    DEVICE_INFO,
    DEVICE_MEMORY,
    DEVICE_TRANSFER_BYTES,
    DEVICE_TRANSFER_BYTES_BY_DEVICE,
    REGISTRY,
    XLA_CACHE_RETRIEVALS,
    XLA_COMPILE_SECONDS,
    XLA_COMPILES,
)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: fired INSIDE backend_compile when the persistent compilation cache
#: serves the executable — that enclosing compile event is a retrieval,
#: not a compilation, and must not count as one (the serving fabric's
#: shared-executable contract is "process 2 compiles nothing", asserted
#: as an xla_compile_total delta of zero)
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: all three events fire on the thread running the compile, so a plain
#: thread-local pairs a retrieval with its enclosing compile event and a
#: compile's start with its end
_compile_tls = threading.local()

#: the stable names of the program's jitted steps and Pallas kernels
#: (PERF.md section 3) — the bounded value set of the `fn` label
KERNEL_NAMES: set = set()


def kernel_name(name: str):
    """Give a jitted step or kernel body ONE name that survives a
    refactor: the function runs under `jax.named_scope(name)` (the name
    prefixes every HLO op's metadata), takes it as `__name__` (so the
    compiled module is `jit_<name>` and jax.monitoring reports it as
    `fun_name`), and joins KERNEL_NAMES. Apply directly under `jax.jit`,
    or to the body handed to `pl.pallas_call(..., name=name)`."""
    def wrap(fn):
        import jax

        @functools.wraps(fn)
        def named(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        named.__name__ = named.__qualname__ = name
        KERNEL_NAMES.add(name)
        return named
    return wrap

_install_lock = threading.Lock()
_installed = False

#: live DeviceCache instances (registered by DeviceCache.__init__) —
#: the memory collector sums their pinned bytes at scrape time
_caches: "weakref.WeakSet" = weakref.WeakSet()


def register_cache(cache) -> None:
    _caches.add(cache)


def _device_label() -> str:
    """platform:id of the device this thread's new arrays land on."""
    import jax

    dev = jax.config.jax_default_device
    if dev is None:
        return f"{jax.default_backend()}:0"
    return f"{dev.platform}:{dev.id}"


def count_h2d(nbytes: int) -> None:
    if nbytes:
        DEVICE_TRANSFER_BYTES.inc(float(nbytes), direction="h2d")
        DEVICE_TRANSFER_BYTES_BY_DEVICE.inc(
            float(nbytes), direction="h2d", device=_device_label())
        ledger.add("h2d_bytes", float(nbytes))


def count_d2h(nbytes: int) -> None:
    if nbytes:
        DEVICE_TRANSFER_BYTES.inc(float(nbytes), direction="d2h")
        DEVICE_TRANSFER_BYTES_BY_DEVICE.inc(
            float(nbytes), direction="d2h", device=_device_label())
        ledger.add("d2h_bytes", float(nbytes))


def _on_scalar(event: str, value, **kwargs) -> None:
    """A backend compile starts on this thread: open its span (and its
    profiler annotation) under the thread's innermost open span."""
    if event != _COMPILE_EVENT:
        return
    # jax reports the compiled module as "jit(<function name>)"
    fn = str(kwargs.get("fun_name") or "")
    if fn.startswith("jit(") and fn.endswith(")"):
        fn = fn[4:-1]
    if fn not in KERNEL_NAMES:
        fn = "eager"
    thread = "request" if tracing.current_trace_id() is not None \
        and not tracing.in_warmup() else "warmup"
    sp = tracing.annotated_span("compile", fn=fn, thread=thread)
    sp.__enter__()
    stack = getattr(_compile_tls, "open", None)
    if stack is None:
        stack = _compile_tls.open = []
    stack.append(sp)


def _on_event_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        pending = getattr(_compile_tls, "cache_hits", 0)
        _compile_tls.cache_hits = pending + 1
        return
    if event != _COMPILE_EVENT:
        return
    import jax

    # a compile event means a backend is up: default_backend() cannot
    # initialise (or fail to initialise) anything here
    backend = jax.default_backend()
    stack = getattr(_compile_tls, "open", None)
    sp = stack.pop() if stack else None
    pending = getattr(_compile_tls, "cache_hits", 0)
    if pending:
        # persistent-cache retrieval wrapped in a compile event: the
        # backend compiled nothing, so the compile counter stays put
        # and the span is not a `compile`
        _compile_tls.cache_hits = pending - 1
        XLA_CACHE_RETRIEVALS.inc(backend=backend)
        if sp is not None:
            sp.name = "compile_cache_load"
            sp.__exit__(None, None, None)
        return
    labels = {"backend": backend, "fn": "eager", "thread": "warmup"}
    if sp is not None:
        labels.update(fn=sp.attrs["fn"], thread=sp.attrs["thread"])
        sp.attrs["seconds"] = round(float(duration_secs), 6)
        sp.__exit__(None, None, None)
    XLA_COMPILES.inc(**labels)
    XLA_COMPILE_SECONDS.observe(float(duration_secs), **labels)


def _collect_device_memory() -> None:
    """Scrape-time gauge refresh (registered on REGISTRY)."""
    cache_bytes = 0
    for cache in list(_caches):
        cache_bytes += getattr(cache, "_bytes", 0)
    DEVICE_MEMORY.set(float(cache_bytes), kind="cache")
    import jax

    devices = jax.local_devices()
    DEVICE_INFO.set(float(len(devices)), platform=devices[0].platform,
                    device_kind=devices[0].device_kind)
    stats = [d.memory_stats() or {} for d in devices]
    if any("bytes_in_use" in st for st in stats):
        # every local device counts: a mesh spreads the hot set
        DEVICE_MEMORY.set(
            float(sum(st.get("bytes_in_use", 0) for st in stats)),
            kind="in_use")
        DEVICE_MEMORY.set(
            float(sum(st.get("bytes_limit", 0) for st in stats)),
            kind="limit")
    else:
        # CPU backend (no PJRT allocator stats): the block cache's pinned
        # bytes ARE the device working set — report them so the series
        # exists with meaning on every backend
        DEVICE_MEMORY.set(float(cache_bytes), kind="in_use")


def install() -> None:
    """Wire the jax.monitoring listener + the memory collector. Safe to
    call from several modules; only the first call does work."""
    global _installed
    with _install_lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_event_duration)
    REGISTRY.register_collector(_collect_device_memory)
