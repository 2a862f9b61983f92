"""Fixture: thread-spawning classes the race rule must NOT flag."""
import threading


class LockedEngine:
    def __init__(self):
        self._cond = threading.Condition()
        self.steps = 0
        self.depth = 0
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            with self._cond:
                self.steps += 1          # locked: fine
                self._bump_locked()

    def _bump_locked(self):
        # `_locked` suffix == caller holds the lock (repo convention)
        self.depth += 1

    def stats(self):
        with self._cond:
            return {"steps": self.steps, "depth": self.depth}


class PrivateState:
    """Thread-private attrs (no public method touches them): fine."""

    def __init__(self):
        self._n = 0
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        self._n += 1


class Suppressed:
    def __init__(self):
        self._lock = threading.Lock()
        self.flag = False
        threading.Thread(target=self._run, daemon=True).start()

    def _run(self):
        while not self.flag:
            pass

    def stop(self):
        self.flag = True  # rtpu: allow[thread-race]


class _CountingLock:
    """A wrapper of the class's own that takes its lock its own way (the
    decode engine's `_LoopLock`: a wait is counted)."""

    def __init__(self, owner):
        self.owner = owner

    def __enter__(self):
        self.owner._lock.acquire()

    def __exit__(self, *exc):
        self.owner._lock.release()


class WrappedLockEngine:
    """The thread takes the lock through an attribute made by a class
    whose name ends in ``Lock``: locked like any other."""

    def __init__(self):
        self._lock = threading.RLock()
        self._mine = _CountingLock(self)
        self.steps = 0
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        with self._mine:
            self.steps += 1

    def stats(self):
        with self._lock:
            return self.steps
