"""A probe of the interpreter lock: what re-taking it costs right now.

One daemon thread sleeps a fixed period and observes how much LATER
than asked it was running again. `time.sleep` gives the interpreter
lock up and has to take it back before it returns — through the same
`take_gil` a request thread goes through after every dispatch, copy and
decode that released it — so the lateness is a sample of what one
re-acquisition costs at that moment, plus the timer's own lateness
(what an idle server reads). It lands in
greptimedb_tpu_interpreter_lock_wait_seconds. The probe holds the lock
for a few microseconds per reading: it is one more waiter, never a
holder. The stage spans' second clock (utils/tracing.py: `cpu_ms`) says
how long each stage's thread was off the CPU; this says how much of
that one wait for the lock can explain.

On where `tracing.enabled()` is (the default), off with
`GTPU_TRACING=off`; started by options.apply_observability, stopped
with the server."""

from __future__ import annotations

import threading
import time
from typing import Optional

from greptimedb_tpu.utils import profiling, tracing
from greptimedb_tpu.utils.metrics import LOCK_WAIT_SECONDS

#: 50 readings a second
PERIOD_S = 0.02


class LockProbe(threading.Thread):
    def __init__(self):
        super().__init__(name="gtpu-lock-probe", daemon=True)
        self._halt = False

    def run(self) -> None:
        # /debug/pprof/cpu leaves the instruments out of its stacks
        me = threading.get_ident()
        profiling.register_profiler_thread(me)
        try:
            while not self._halt:
                t0 = time.perf_counter()
                time.sleep(PERIOD_S)
                late = time.perf_counter() - t0 - PERIOD_S
                LOCK_WAIT_SECONDS.observe(max(late, 0.0))
        finally:
            profiling.unregister_profiler_thread(me)

    def stop(self) -> None:
        self._halt = True
        self.join(timeout=2.0)


_PROBE: Optional[LockProbe] = None
_install_lock = threading.Lock()


def running() -> bool:
    return _PROBE is not None and _PROBE.is_alive()


def maybe_install() -> None:
    """Start the process's probe where tracing is on, stop it where it
    is off (idempotent; options.apply_observability calls it)."""
    global _PROBE
    if not tracing.enabled():
        return shutdown()
    with _install_lock:
        if not running():
            _PROBE = LockProbe()
            _PROBE.start()


def shutdown() -> None:
    global _PROBE
    with _install_lock:
        probe, _PROBE = _PROBE, None
    if probe is not None:
        probe.stop()
