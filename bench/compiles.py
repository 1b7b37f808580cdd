"""Compilations seen through ``jax.monitoring``: the count of backend
compiles (a persistent-cache retrieval included) and the cache's hits.
The harness reads the count at the window's edges, and the names of what
compiled there: no program may compile inside the window."""
from __future__ import annotations

import jax


class CompileLog:
    def __init__(self):
        self.programs = 0
        self.hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.programs += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class CompileNames:
    """Names of the programs compiled while the context is open (JAX's
    ``jax_log_compiles`` records), so that a compile in the window is
    named and not only counted."""

    def __init__(self):
        self.names: list[str] = []

    def __enter__(self):
        import logging

        names = self.names

        class _Handler(logging.Handler):
            def emit(self, record):
                if record.msg.startswith("Compiling") and record.args:
                    names.append(str(record.args[0]))

        self._handler = _Handler()
        self._logger = logging.getLogger("jax")
        self._logger.addHandler(self._handler)
        jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc):
        jax.config.update("jax_log_compiles", False)
        self._logger.removeHandler(self._handler)
        return False
