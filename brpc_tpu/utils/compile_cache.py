"""Where the XLA persistent compilation cache lives.

Every entry point that compiles at width — ``chip_smoke.py``,
``bench.py``'s device children, the training examples — calls
:func:`enable_compile_cache` before its first compilation, so a second
run of the same programs loads executables instead of recompiling them.

The directory is placed from OUTSIDE when ``JAX_COMPILATION_CACHE_DIR``
is set (JAX reads it into ``jax_compilation_cache_dir`` itself — this
helper then sets nothing).  Otherwise it is ONE fixed, git-ignored
directory inside the checkout: the path is part of the cache key, so a
directory that moves between runs (tempfile, pid, timestamp) never
hits.
"""

from __future__ import annotations

import os

# <checkout>/.jax_compile_cache — brpc_tpu/utils/ is two levels below
# the checkout root
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Call before the first compilation of the process (JAX binds the
    cache at first use)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


class CompileMeter:
    """What this process has spent getting programs ready to run, read
    from JAX's own monitoring events: seconds tracing + lowering +
    backend-compiling (a persistent-cache load counts as its load
    time), and the persistent cache's hits and misses.  Create one
    before the first compilation; read the fields at any time."""

    _COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration",
                       "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event in self._COMPILE_EVENTS:
            self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1
