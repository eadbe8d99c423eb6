"""Spans on the served path, recorded by ``jax.profiler``.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: with no
profiler running it costs about a microsecond; under
``jax.profiler.start_trace`` it lands on the ``/host:CPU`` plane of the
trace, on the same clock as the device planes, with ``args`` among the
event's stats.  The profiler's trace is the only exporter (TensorBoard and
Perfetto read it).

Catalogue.  Each name is emitted by one kind of thread only: a client
thread (the caller of the router; the in-process REST hop runs the replica's
handler on it) or a replica's pump thread (``serve_job``).  Nothing is finer
than one span per request or per decode tick.

=====================  ======  ==============================================
span                   thread  brackets (args)
=====================  ======  ==============================================
``router.request``     client  ``ServiceEndpoint.request``: the whole call,
                               picks and retries included
``replica.request``    client  ``serve_job``'s handler, entry to return
``replica.enqueue``    client  taking the replica's lock, ``submit`` and the
                               wake-up of the pump
``replica.wait``       client  from submit until the result is popped
                               (``rid``)
``replica.step``       pump    one locked pump iteration that steps the
                               engine: ``ServingEngine.step`` and the
                               hand-off of its finished requests
``replica.idle``       pump    one wait on the lock's condition taken because
                               the engine has nothing to do
``engine.step``        pump    ``ServingEngine.step``
``engine.admit``       pump    one admission: pad, prefill dispatch, ``pos``
                               rewind, insert dispatch (``rid``,
                               ``prompt_len``)
``engine.decode``      pump    one decode tick (``active``, ``pending``)
``engine.sample``      pump    inside ``engine.decode``: the argmax and its
                               pull to the host, where the host waits for
                               the device
``engine.retire``      pump    inside ``engine.decode``: the per-slot append
                               and finish loop, with its ``pos`` reads
=====================  ======  ==============================================
"""
from __future__ import annotations


def span(name: str, **args):
    """A context manager that records ``name`` (and ``args``) as one host
    event of the profiler's trace.  JAX is imported here, not with the
    module: the router and the control plane import no JAX otherwise."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **args)
