"""The router's balance over replicas: the requests the busiest replica
took in the window over the mean per replica (1.0 when every replica took
as many).  Read from the router's per-replica counters
(``ServiceEndpoint.stats()``) before and after the window; nothing with one
replica."""


def read(ctx):
    took = ctx.replica_requests
    if not took or len(took) < 2 or not sum(took):
        return None
    return max(took) / (sum(took) / len(took))
