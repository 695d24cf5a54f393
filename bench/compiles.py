"""Count the programs JAX compiles, or loads from its cache, in a block.

Every executable JAX obtains, by compiling it or by reading it from the
persistent cache, passes ``/jax/core/compile/backend_compile_duration``;
inside a measured window either is a stall the window should not have.
"""
from __future__ import annotations

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """``with CompileCounter() as c: ...`` then ``c.count``."""

    def __init__(self):
        self.count = 0

    def _listen(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False
