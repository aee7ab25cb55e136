"""Counts XLA compilations with jax.monitoring, so that no flag of the
program has to be switched on in the measured path."""


class CompileCounter:
    """`n`: programs handed to the backend compiler, cache hits included
    (each is a program that was not ready); `cache_misses`: those the
    persistent cache did not hold."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _seconds, **_):
        if event == self.COMPILE:
            self.n += 1

    def _event(self, event, **_):
        if event == self.MISS:
            self.cache_misses += 1
