"""profiled_jit — the one blessed ``jax.jit`` call site in the tpu layer.

Every compiled program registers through the amprof observatory so
recompiles, dispatch latencies and shape buckets carry program identity
(obs/prof.py). amlint AM306 flags any other ``jax.jit`` call in the
package; the call below is exempt because it feeds
``Observatory.register`` directly.

Usage (decorator keywords pass straight through to ``jax.jit``; the
static-argument layout is visible to amlint's tracer rules exactly as it
was on a bare ``@partial(jax.jit, ...)``)::

    @profiled_jit("paging.apply_ops", static_argnames=("page_size",),
                  donate_argnums=(0,))
    def paged_apply_ops(slab, ...):
        ...

It also registers ``profiler_mark`` as amtrace's timeline factory
(obs/spans.py): while whoever runs ``jax.profiler`` has a trace
recording, every span, interval and collection of an enabled amtrace
trace becomes an ``am.<name>`` annotation on the device lines' clock.
With no profiler trace recording, nothing is annotated.
"""
from __future__ import annotations

import jax

from ..obs.prof import ProfiledProgram, get_observatory
from ..obs.spans import set_timeline


def profiler_mark(name: str, **args):
    """A ``jax.profiler.TraceAnnotation`` while a profiler trace records,
    else None (one check, nothing allocated)."""
    if jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name, **args)
    return None


set_timeline(profiler_mark)


def profiled_jit(name: str, **jit_kwargs):
    """Decorator: jits ``fn`` and registers it on the process observatory
    under ``name``. Returns the :class:`ProfiledProgram` wrapper (calls
    fall through to the jitted function while the observatory is
    disabled)."""

    def wrap(fn) -> ProfiledProgram:
        return get_observatory().register(name, jax.jit(fn, **jit_kwargs))

    return wrap
