"""Device-side data plane (JAX/XLA).

Importing this package configures JAX for the engine — the one place
that does:

* x64 on, because timestamps are int64 nanoseconds end-to-end (f32/i32
  cannot represent them) and integer fields are i64.
* persistent compilation cache: where `JAX_COMPILATION_CACHE_DIR` is set
  JAX already reads it and no directory is set here; otherwise the cache
  lives at `<checkout>/.jax_cache`, a fixed path (the path is part of
  the cache key, so one that moves never hits). The served path is many
  small programs (one per filter/aggregate/shape class), so every
  compile is kept, not only those above JAX's one-second default.

* every jitted program has a stable name on the device (`program`).

Host-only layers (models/storage) do not import this, keeping
pure-metadata use of cnosdb_tpu jax-free.
"""
import functools
import os
import threading

import jax

jax.config.update("jax_enable_x64", True)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

# persistent-cache outcomes of this process's compiles, from JAX's own
# monitoring events — cnosdb_compile_cache_total{outcome} on /metrics
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}
_cache_lock = threading.Lock()
_cache_counts = {"hit": 0, "miss": 0}


def _on_jax_event(event: str, **_kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome is not None:
        with _cache_lock:
            _cache_counts[outcome] += 1


jax.monitoring.register_event_listener(_on_jax_event)


def program(name: str):
    """Give a to-be-jitted function a stable identity on the device:
    `__name__` "cnosdb_<name>" (so its XLA module is `jit_cnosdb_<name>`,
    whatever closure or lambda it came from) and its whole body under
    `jax.named_scope("cnosdb.<name>")` (so every HLO operation's op name
    carries the program in a profiler trace). Metadata only: the compiled
    code is the same."""
    def deco(fn):
        @functools.wraps(fn)
        def body(*args, **kwargs):
            with jax.named_scope("cnosdb." + name):
                return fn(*args, **kwargs)
        body.__name__ = body.__qualname__ = "cnosdb_" + name
        return body
    return deco


def compile_cache_dir() -> str | None:
    """The directory JAX's persistent compilation cache writes to (None
    when the cache is switched off, as the test suite does)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir


def compile_cache_snapshot() -> dict[str, int]:
    with _cache_lock:
        return dict(_cache_counts)
