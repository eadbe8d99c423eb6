"""The wait to hand a request to the replica: the time in
``replica.enqueue`` (taking the replica's lock, the submit and the pump's
wake-up) over the count of ``replica.request`` spans.  ``devtrace.load``
drops host events under 50 us, so such an enqueue counts as 0: the
reading is low by at most 0.05 ms a request."""
from bench import spans


def read(ctx):
    t = spans.traced(ctx)
    if t is None:
        return None
    waited, _ = spans.total(t, spans.REPLICA_ENQUEUE)
    _, n = spans.total(t, spans.REPLICA_REQUEST)
    return waited / n / 1e6 if n else None
