"""The three places where the framework leans on JAX internals, process
configuration or the interpreter's: the axis-environment query, the
persistent compile cache's location, and room on CPython's frame stack
for a call that traces and lowers a program."""

from __future__ import annotations

import os
import types

__all__ = ["axis_env_contains", "configure_persistent_cache",
           "call_with_frame_room"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def axis_env_contains(name):
    """True when ``name`` is bound as a mapped axis by an enclosing
    ``shard_map``/``pmap`` of the current trace — the explicit check
    behind ``Communicator._axis_in_scope`` (no traced-probe-and-catch).
    A jax upgrade that moves the query surfaces as an ImportError here,
    not as a silent flip of eager/traced mode selection."""
    from jax._src.core import get_axis_env
    return bool(get_axis_env().axis_exists(name))


def configure_persistent_cache():
    """Turn on JAX's persistent compile cache; the one place that
    decides where it lives.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no
    directory is set in code.  Unset: ``<checkout>/.jax_cache``,
    resolved from this package's location — the path is part of the
    cache key, so it must not depend on the cwd, the pid or the clock.
    Returns the directory in effect.  (Warm-cache replays of scan and
    params-donated step programs were re-run clean on jax 0.9.0's CPU
    backend, so no program kind is excluded.)
    """
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax
    cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def _call(fn, args, kwargs):
    return fn(*args, **kwargs)


# ``_call`` under a code object that declares an evaluation stack of
# 2**16 slots: a frame of 512 KiB, which CPython cannot fit into the
# chunk it is in, so it allocates one of 1 MiB for it, and the 512 KiB
# behind the frame are where everything ``fn`` calls gets its frames
_roomy_call = types.FunctionType(
    _call.__code__.replace(co_stacksize=1 << 16), globals(),
    "_roomy_call")


def call_with_frame_room(fn, *args, **kwargs):
    """``fn(*args, **kwargs)``, its frames and those of all it calls
    laid into one fresh 1 MiB chunk of CPython's frame stack (a few
    thousand frames deep), for a call that traces and lowers a large
    program.

    CPython (3.11 and later) keeps Python frames in chunks of 16 KiB,
    takes a chunk from the system (``mmap``) when a call finds no room
    in the current one and gives it back (``munmap``) when that call
    returns.  A loop whose frame is the last to fit pays both for EVERY
    call it makes.  JAX's lowering is a recursion a few hundred frames
    deep with such loops at every level, so where the chunks' edges
    fall, which follows from nothing but the depth of the caller's
    stack, decides whether a large program lowers in one second or in
    twenty on a machine where the two system calls are dear (a
    many-threaded process in a virtual machine: PERF.md section 6,
    PR 46, has the readings).  One call costs the two system calls
    once, about 10 us: make it where a program is about to be traced,
    not in a loop that is already warm."""
    return _roomy_call(fn, args, kwargs)
