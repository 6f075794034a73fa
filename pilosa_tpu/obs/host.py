"""The `host` group of /debug/vars: what the whole process spent.

`cpu_s` is the CPU time of every thread of the process (the serving
threads, the runtime's own, the collector inside whichever thread it ran
on); the `gc_*` keys count the cyclic collector's runs and the seconds
they took, through one `gc.callbacks` entry. Beside a count of answers
they give CPU and collector time an answer, which no span can: a span
reads one thread. Read when asked; nothing here is on a request's path
but the two callback calls a collection.
"""

from __future__ import annotations

import gc
import time


class HostMeter:
    """Counts the collector's runs from start() to close(). The collector
    runs one collection at a time, under the interpreter lock, and calls
    back `start` then `stop` on the thread that set it off: plain bumps."""

    def __init__(self):
        self._began = None
        self.gc_collections = 0
        self.gc_full_collections = 0
        self.gc_s = 0.0

    def start(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._began = time.monotonic()
        elif self._began is not None:
            # (None: the meter was started while a collection ran.)
            self.gc_s += time.monotonic() - self._began
            self._began = None
            self.gc_collections += 1
            if info["generation"] == 2:
                self.gc_full_collections += 1

    def snapshot(self) -> dict:
        return {
            "cpu_s": time.process_time(),
            "gc_collections": self.gc_collections,
            "gc_full_collections": self.gc_full_collections,
            "gc_s": self.gc_s,
        }
