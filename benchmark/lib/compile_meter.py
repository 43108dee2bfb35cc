"""Counts jax backend compiles from jax's own monitoring events, every
thread included (a copy of chip_smoke.py's CompileMeter, PR 21).  A
retrieval from the persistent cache still counts: it is a compile
request on the path."""
import threading
import time

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.last = 0.0           # time.monotonic() of the last compile
        self.names = []           # what was compiled, in order
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, fun_name=None, **_):
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1
                self.seconds += secs
                self.last = time.monotonic()
                self.names.append(str(fun_name))

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def mark(self):
        with self._lock:
            return (self.compiles, self.seconds, self.cache_hits)

    def since(self, mark):
        now = self.mark()
        with self._lock:
            names = self.names[mark[0]:]
        return {"compiles": now[0] - mark[0], "names": names,
                "compile_s": now[1] - mark[1],
                "cache_hits": now[2] - mark[2]}
