"""Named host spans of the serving path, on the device trace's clock.

Each span is a :class:`jax.profiler.TraceAnnotation`: while a profiler
session is active it lands on the trace's host plane beside the device's
programs, with the counters known when it opens as its stats; otherwise it
records nothing and costs about a microsecond.  The spans sit at the
boundaries that ``ContinuousStats`` already times, so every idle gap of a
device trace falls inside a named host phase:

==================  ========================================  ==============
span                where                                     stats
==================  ========================================  ==============
``frontend.pick``   ``_serve_loop`` selects a wave            wave, n
``frontend.wave``   ``runtime.serve`` on the worker thread    wave, n
``frontend.finish`` the wave's outputs are finished           wave, n
``runtime.link``    the link model's rates and hop prices     wave
``runtime.split``   split solve, controller and router        wave
``runtime.group_run`` one group's engine runs of a wave       group, wave, n
``engine.prefill``  one B=1 prefill dispatch                  uid, inline
``engine.boundary`` pad and ``admit_boundary`` dispatch       admitted, live,
                                                              stall
``engine.launch``   the decode macro-step's launch            live,
                                                              cache_inplace
``engine.topup``    speculative shadow prefills               shadows
``engine.await``    the one host sync of a macro-step         (none)
==================  ========================================  ==============
"""
from __future__ import annotations

import jax

NAMES = ("frontend.pick", "frontend.wave", "frontend.finish",
         "runtime.link", "runtime.split", "runtime.group_run",
         "engine.prefill", "engine.boundary", "engine.launch",
         "engine.topup", "engine.await")
_KNOWN = frozenset(NAMES)


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` carrying ``stats``.  Only the names in
    :data:`NAMES` are taken, so that a reader of the trace knows every
    span the program can write."""
    if name not in _KNOWN:
        raise ValueError(f"unknown span {name!r}: add it to spans.NAMES")
    return jax.profiler.TraceAnnotation(name, **stats)
