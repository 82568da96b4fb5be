"""On-demand profiling over HTTP — the pprof analog.

Mirrors the reference's `servers/src/http/pprof.rs` (CPU profiles via
the pprof crate's sampling profiler) and `http/mem_prof.rs` (jemalloc heap
profiles): here a wall-clock stack sampler over `sys._current_frames()`
produces folded-stack output (the "collapsed" format speedscope and
other stack-graph renderers read), and tracemalloc snapshots provide allocation profiles. Both are
pull-style: hit the endpoint, get a self-contained text artifact.

`device_trace` is the accelerator's counterpart: it brackets a few
seconds with `jax.profiler`, host tracer on, so the `.xplane.pb` it
leaves holds the device's operations AND the program's spans (every
`tracing.span` is a TraceAnnotation) on one clock; `tools/trace_gaps.py`
reduces it."""

from __future__ import annotations

import os
import sys
import threading
import time
import tracemalloc
from collections import Counter

#: thread idents of every live instrument thread — this module's
#: on-demand sampler, utils/lock_probe.py's probe — each registers
#: itself so no profile is ever polluted by the instruments observing
#: each other. Plain set mutations are GIL-atomic.
_PROFILER_TIDS: set = set()


def register_profiler_thread(tid: int) -> None:
    _PROFILER_TIDS.add(tid)


def unregister_profiler_thread(tid: int) -> None:
    _PROFILER_TIDS.discard(tid)


def sample_cpu(seconds: float = 5.0, hz: float = 99.0,
               include_idle: bool = False) -> str:
    """Sample every thread's Python stack for `seconds` at `hz`.

    Returns folded stacks: `frame;frame;...;leaf count` per line, leaf
    last — feed to any stack-graph renderer. Threads blocked in epoll/GIL
    waits are skipped unless include_idle (matching pprof's on-CPU view
    as closely as a wall sampler can). Instrument threads — this one and
    any registered one (the interpreter-lock probe) — are excluded: an
    earlier version counted its own sampling loop when invoked off the
    serving thread, so every profile carried a phantom `sample_cpu`
    tower."""
    deadline = time.monotonic() + seconds
    interval = 1.0 / hz
    stacks: Counter = Counter()
    me = threading.get_ident()
    register_profiler_thread(me)
    try:
        n_samples = _sample_loop(deadline, interval, stacks, include_idle)
    finally:
        unregister_profiler_thread(me)
    lines = [f"# sampler: {n_samples} samples @ {hz:g}Hz over {seconds:g}s"]
    for stack, count in stacks.most_common():
        lines.append(f"{stack} {count}")
    return "\n".join(lines) + "\n"


def _sample_loop(deadline: float, interval: float, stacks: Counter,
                 include_idle: bool) -> int:
    n_samples = 0
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid in _PROFILER_TIDS:
                continue
            parts = []
            f = frame
            while f is not None:
                code = f.f_code
                parts.append(f"{code.co_name} ({code.co_filename.rsplit('/', 1)[-1]}:{f.f_lineno})")
                f = f.f_back
            if not parts:
                continue
            leaf = parts[0]
            if not include_idle and (
                "wait" in leaf or "select" in leaf or "poll" in leaf
                or "accept" in leaf or "read (" in leaf
            ):
                continue
            stacks[";".join(reversed(parts))] += 1
        n_samples += 1
        time.sleep(interval)
    return n_samples


_mem_lock = threading.Lock()


def mem_profile(top: int = 50) -> str:
    """Allocation snapshot (jemalloc heap-profile analog). Starts
    tracemalloc on first call — the first snapshot covers allocations from
    then on; subsequent calls show current live allocations."""
    with _mem_lock:
        if not tracemalloc.is_tracing():
            tracemalloc.start(10)
            return ("# tracemalloc started; allocations recorded from now —"
                    " call again for a snapshot\n")
        snap = tracemalloc.take_snapshot()
    stats = snap.statistics("lineno")
    total = sum(s.size for s in stats)
    lines = [f"# live python allocations: {total / 1e6:.1f} MB "
             f"in {len(stats)} sites (top {top})"]
    for s in stats[:top]:
        fr = s.traceback[0]
        lines.append(f"{s.size / 1e3:.1f}kB x{s.count} "
                     f"{fr.filename.rsplit('/', 1)[-1]}:{fr.lineno}")
    return "\n".join(lines) + "\n"


def mem_profile_stop() -> str:
    with _mem_lock:
        if tracemalloc.is_tracing():
            tracemalloc.stop()
            return "# tracemalloc stopped\n"
        return "# tracemalloc was not running\n"


class ProfilerBusy(RuntimeError):
    """A profiler session is already running in this process (another
    caller's, or one the launcher holds): one at a time."""


_device_lock = threading.Lock()


def device_trace(seconds: float, base_dir: str) -> dict:
    """Trace the next `seconds` seconds with jax.profiler (device planes
    + host tracer, python tracer off) into a new directory under
    `base_dir`; returns {dir, t_start_ns, t_stop_ns} (unix clock, around
    the session). Only the process that holds the chip can trace it, so
    this runs in the server. Raises ProfilerBusy while any session is
    open."""
    import jax

    if not _device_lock.acquire(blocking=False):
        raise ProfilerBusy("a device profile is already being taken")
    try:
        out = os.path.join(
            base_dir, time.strftime("device-%Y%m%dT%H%M%S")
            + f"-{time.time_ns() % 1_000_000:06d}")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        try:
            jax.profiler.start_trace(out, profiler_options=opts)
        except RuntimeError as e:
            # jax allows one session per process: someone else's is open
            raise ProfilerBusy(str(e)) from e
        t_start = time.time_ns()
        try:
            time.sleep(seconds)
        finally:
            t_stop = time.time_ns()
            jax.profiler.stop_trace()
        return {"dir": out, "t_start_ns": t_start, "t_stop_ns": t_stop}
    finally:
        _device_lock.release()
