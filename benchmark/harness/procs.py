"""Every process a run starts ends with the run, on every way out of it.

Three holds, because no one of them covers every way out:

* `die_with(parent)` — each child the harness starts (the bulk-load
  helper, the serving process, the trace reducer) asks the kernel to
  SIGKILL it when the harness process dies, however that dies (SIGKILL
  included, which no handler of the harness sees).
* `Guard` — the harness turns SIGTERM / SIGINT / SIGHUP and its own
  deadline (SIGALRM) into `Stopped`, so that a run that is told to stop,
  or that overruns the time the contract gives a run, leaves through its
  `finally` clauses instead of dying with its server alive.
* `reap_all()` — the harness is the sub-reaper of its descendants: one
  whose parent died (a worker of the server) becomes the harness's child
  instead of init's. On the way out whatever is still alive below the
  harness is killed and *waited for*, so that no process — not a dying
  one, not a zombie — is there when the harness's own exit is seen.

Children stay in the harness's process group and session: whoever kills
that group (a time limit) kills them with it.
"""

from __future__ import annotations

import ctypes
import os
import signal
import time

PR_SET_PDEATHSIG = 1
PR_SET_CHILD_SUBREAPER = 36

#: seconds a run may take from its start to its exit (the contract: a
#: checkout's first run, which compiles, 1200) and from the end of
#: set-up to its exit beyond the window (the contract: 360 in all)
LIMIT_WHOLE_S = 1150
LIMIT_AFTER_SETUP_S = 180


class Stopped(BaseException):
    """The run was told to stop (a signal) or overran its deadline."""


def _prctl(option: int, value: int) -> None:
    libc = ctypes.CDLL(None, use_errno=True)
    if libc.prctl(option, value, 0, 0, 0) != 0:
        raise OSError(ctypes.get_errno(), "prctl")


def die_with(parent: int) -> None:
    """Called first thing by a child: SIGKILL me when `parent` dies."""
    _prctl(PR_SET_PDEATHSIG, signal.SIGKILL)
    if os.getppid() != parent:      # it died before the line above
        os._exit(1)


def children_of(root: int) -> list:
    """Every live or zombie descendant of `root`, parents first."""
    kids: dict = {}
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat", "rb") as f:
                ppid = int(f.read().rsplit(b")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        kids.setdefault(ppid, []).append(int(d))
    out, todo = [], [root]
    while todo:
        for k in kids.get(todo.pop(), []):
            out.append(k)
            todo.append(k)
    return out


def end(pids: list, timeout_s: float = 60.0) -> None:
    """SIGKILL each of `pids` and wait until none of them is there any
    more, not as a zombie either (orphans are this process's children:
    it is their sub-reaper)."""
    t_end = time.monotonic() + timeout_s
    left = list(pids)
    while left:
        for pid in left:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
            try:
                os.waitpid(pid, os.WNOHANG)
            except OSError:
                pass
        left = [pid for pid in left if os.path.exists(f"/proc/{pid}")]
        if left and time.monotonic() > t_end:
            raise OSError(f"processes {left} outlive SIGKILL")
        if left:
            time.sleep(0.01)


def reap_all() -> list:
    """End every descendant. Returns the pids that were still there
    (none, after a run that stopped what it started)."""
    found: list = []
    while True:
        left = children_of(os.getpid())
        if not left:
            return found
        found.extend(p for p in left if p not in found)
        end(left)


class Guard:
    """`with Guard():` around a whole run. See the module's text."""

    SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP, signal.SIGALRM)

    def _stop(self, signum, _frame) -> None:
        raise Stopped(
            "deadline passed" if signum == signal.SIGALRM
            else f"stopped by {signal.Signals(signum).name}")

    def __enter__(self):
        _prctl(PR_SET_CHILD_SUBREAPER, 1)
        self._old = {s: signal.signal(s, self._stop) for s in self.SIGNALS}
        signal.alarm(LIMIT_WHOLE_S)
        return self

    def set_up_done(self, seconds: float) -> None:
        signal.alarm(int(seconds) + LIMIT_AFTER_SETUP_S)

    def __exit__(self, *exc) -> None:
        # nothing interrupts the way out
        signal.alarm(0)
        for s in self.SIGNALS:
            signal.signal(s, signal.SIG_IGN)
        try:
            self.killed = reap_all()
        finally:
            for s, old in self._old.items():
                signal.signal(s, old)
            _prctl(PR_SET_CHILD_SUBREAPER, 0)
