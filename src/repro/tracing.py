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
``replica.enqueue``    client  the cancel check and the put of the request
                               and its result on the pump's inbox, a queue
                               the pump never holds across device work
``replica.wait``       client  from that put until the result is handed
                               back (``rid``: the handler's ticket, not the
                               engine's request id)
``replica.step``       pump    one pump iteration that steps the engine: the
                               drain of the inbox into ``submit``,
                               ``ServingEngine.step`` and the hand-off of its
                               finished requests; no lock is held
``replica.idle``       pump    one blocking wait on the inbox, taken because
                               the engine has nothing to do
``engine.step``        pump    ``ServingEngine.step``
``engine.admit``       pump    one admission: pad, prefill dispatch, ``pos``
                               rewind, insert dispatch (``rid``,
                               ``prompt_len``, ``decoding``: the other slots
                               already decoding when it began; 0 at a wave's
                               start, 1 or more when it joins a running
                               batch, counted in the engine's ``joins``)
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
