"""A run ends when its processes have.

Every process a run starts, through the runtime or not, inherits one thing from ``run.py``: an
environment variable that holds a marker unique to the run, set before the runtime starts (the
multiprocessing forkserver is started after it, and the workers are its children). Not the parent
pid: workers are the forkserver's children and become orphans when it goes. Not a session of the
run's own: that would take ``run.py`` out of the process group a caller may kill it by. The
marker is no switch: nothing reads it but this file, from ``/proc/<pid>/environ``.

A process that is exiting has already given up its memory, and with it its ``environ`` and its
command line, while it still closes its files, and closing the chip's device file is the slow
part: it then reads as a zombie with no command line (state Z: the thread that led it has ended,
others have not), for up to ten seconds after a training run on the v5e (PERF.md, PR 28). So the
marker cannot be looked for only at the end: a watcher thread lists ``/proc`` every two seconds
all through the run and remembers who carried the marker (pid, start time, command line). At the
end the remembered processes are waited for by pid until ``/proc/<pid>`` is gone, whatever state
they read as. Gone means collected by a parent: ``run.py`` makes itself the one that orphans of
its own are handed to (a "child subreaper": no new session, no new process group), and collects
them here, so that it does not hang on how often the machine's init looks.
"""

from __future__ import annotations

import ctypes
import os
import signal
import threading
import time

ENV = "BENCHMARK_RUN_MARKER"
WAIT_S = 20.0       # how long the run's processes get to end by themselves once the driver has returned
KILLED_WAIT_S = 60.0  # how long a process that was sent SIGKILL gets to be gone (it may be closing a chip)


def _stat(pid: int):
    """-> (state, ppid, start time in clock ticks) of a live process, or None."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            rest = f.read().rsplit(b")", 1)[1].split()
        return rest[0].decode(), int(rest[1]), int(rest[19])
    except (OSError, IndexError, ValueError):
        return None


def adopt_orphans(on: bool) -> None:
    """prctl(PR_SET_CHILD_SUBREAPER): descendants of this process whose parent ends are handed to
    this process, not to init, so ``reap`` can collect them the moment they have ended."""
    ctypes.CDLL(None, use_errno=True).prctl(36, int(on), 0, 0, 0)


class Reaper:
    def __init__(self, period_s: float = 2.0):
        self.marker = f"{os.getpid()}-{time.time_ns()}-{os.urandom(4).hex()}"
        self._needle = f"{ENV}={self.marker}".encode()
        self._period_s = period_s
        self.seen: dict[int, dict] = {}  # pid -> {"start": its start time in clock ticks, "who": pid, parent, command line}
        self._unmarked: set[int] = set()
        self._stop = threading.Event()
        self._thread = None
        self._lock = threading.Lock()

    def start(self) -> None:
        """Put the marker into this process's environment, for every process started from now on
        to inherit, and start looking for them."""
        os.environ[ENV] = self.marker
        adopt_orphans(True)
        self._thread = threading.Thread(target=self._watch, daemon=True, name="bench-reaper")
        self._thread.start()

    def _watch(self):
        while not self._stop.wait(self._period_s):
            self.scan()

    def scan(self) -> None:
        """Remember every process not looked at before whose initial environment holds the marker."""
        me = os.getpid()
        with self._lock:
            for name in os.listdir("/proc"):
                if not name.isdigit():
                    continue
                pid = int(name)
                if pid == me or pid in self.seen or pid in self._unmarked:
                    continue
                st = _stat(pid)
                try:
                    with open(f"/proc/{pid}/environ", "rb") as f:
                        marked = self._needle in f.read().split(b"\0")
                    with open(f"/proc/{pid}/cmdline", "rb") as f:
                        cmdline = f.read().replace(b"\0", b" ").decode(errors="replace").strip()[:120]
                except OSError:  # gone meanwhile, or another user's
                    continue
                if st is None:
                    continue
                if marked:
                    self.seen[pid] = {"start": st[2], "who": f"{pid} (child of {st[1]}): {cmdline}"}
                elif st[1] != me:
                    # a child of this process between fork and exec still shows this process's own
                    # environment, which the marker was put into after it started: look again
                    self._unmarked.add(pid)

    def alive(self, collect: bool = False) -> list[int]:
        """The remembered processes that are still in ``/proc``, in whatever state: what reads as a
        zombie may still be closing the chip. With ``collect``, one that has ended and is this
        process's to collect is collected first. A pid given to another process since is gone."""
        out = []
        for pid, rec in self.seen.items():
            if collect:
                try:
                    os.waitpid(pid, os.WNOHANG)
                except ChildProcessError:  # another's child, or collected already
                    pass
            st = _stat(pid)
            if st is not None and st[2] == rec["start"]:
                out.append(pid)
        return out

    def close(self) -> list[int]:
        """Stop watching, look one last time, -> the marked processes alive now."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        self.scan()
        return self.alive()

    def reap(self, found: list[int], t_down: float, wait_s: float = WAIT_S, killed_wait_s: float = KILLED_WAIT_S) -> dict:
        """Wait up to ``wait_s`` for every marked process to end, SIGKILL what is left, wait until
        it is gone. ``found``: what ``close()`` gave when the driver returned, at ``t_down`` (its
        last act is ``ray_tpu.shutdown()``). -> how many those were, who they were, how long
        the last of them outlived ``t_down``, which were killed, and which, if any, would not go."""
        left, gone_at = self.alive(collect=True), {pid: time.time() for pid in found}

        def wait(deadline):
            nonlocal left
            while left and time.time() < deadline:
                time.sleep(0.05)
                left = self.alive(collect=True)
                gone_at.update({pid: time.time() for pid in left})

        wait(time.time() + wait_s)
        killed = list(left)
        for pid in killed:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        wait(time.time() + killed_wait_s)
        return {"found": len(found), "who": [self.seen[p]["who"] for p in found],
                "outlived_s": max((t - t_down for t in gone_at.values()), default=0.0),
                "killed": [self.seen[p]["who"] for p in killed], "left": left, "ever": len(self.seen)}
