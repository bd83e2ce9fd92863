"""Profiler integration: jax.profiler as the one tracing system.

The profiler's session is the switch, its buffer the in-memory store, its
``.xplane.pb`` what is written out at the end, and its clock the one the
device trace is on. The program says what the host is doing with
:func:`span`; device-side sections are ``jax.named_scope`` (metadata on the
compiled ops, nothing at run time). :func:`register_program` /
:func:`programs` are the lazy way to the compiled programs whose op names
carry those scopes.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Iterator

import jax

__all__ = [
    "profile_trace",
    "span",
    "SPAN_PREFIX",
    "register_program",
    "programs",
    "shapes_of",
]

#: every host span of the program is named ``pdt.<layer>.<what>``
SPAN_PREFIX = "pdt."


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace to ``log_dir`` (torch.profiler.profile
    role). While it runs every :func:`span` is in the trace, on the clock
    of the device's own events. View with TensorBoard or xprof, or read
    the ``.xplane.pb`` with ``jax.profiler.ProfileData``. The Python tracer
    stays off: it slows the host that is being measured and fills the file
    with frames nobody reads; the host's own events (the runtime's calls
    under a span) are kept."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, create_perfetto_link=False,
                             profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str, **stats: Any):
    """A host span ``pdt.<name>`` in the profiler's trace; with no profiler
    session a no-op of about half a microsecond. ``stats`` arrive as the
    event's statistics: values already at hand (an ``int``, a Python
    counter), never a device read. Request-scoped spans carry
    ``request_id``, step-scoped ones ``step``; the cause of a span is the
    span that encloses it. ``with span(...) as s`` gives the annotation:
    ``s.set_metadata(...)`` adds a stat known only when the work is done."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **stats)


def shapes_of(tree):
    """``tree`` with every leaf replaced by its ``jax.ShapeDtypeStruct`` (a
    committed array keeps its sharding): what a :func:`register_program`
    thunk saves in place of the arrays, so that it holds no memory."""
    def one(a):
        if isinstance(a, jax.Array):
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=a.sharding if a.committed else None)
        return jax.ShapeDtypeStruct(jax.numpy.shape(a), jax.numpy.result_type(a))

    return jax.tree_util.tree_map(one, tree)


_PROGRAMS: Dict[str, Callable[[], Any]] = {}


def register_program(name: str, thunk: Callable[[], Any]) -> None:
    """Name a way to a compiled program: ``thunk()`` lowers and compiles it
    from saved shapes when somebody asks, and nothing before. The newest
    registration of a name wins (one runner, one engine a process is the
    deployed case)."""
    _PROGRAMS[name] = thunk


def programs() -> Dict[str, Callable[[], Any]]:
    """The registered thunks by name: ``programs()["step"]()`` is the
    compiled train step (``as_text()`` carries each op's ``op_name`` with
    its named scopes, ``memory_analysis()`` its bytes); the engine gives
    ``"decode"`` and ``"prefill/<bucket>"``."""
    return dict(_PROGRAMS)
