"""sharded.collective_ms: device milliseconds of collective operations
(all-gather, all-reduce, collective-permute and the like, synchronous
or async) on the first chip of the mesh in the traced window, per
served call."""

COLLECTIVES = (r"^%?(all-gather|all-reduce|reduce-scatter|"
               r"collective-permute|all-to-all)")


def read(ctx):
    if not ctx.calls:
        return None
    ns = ctx.trace.op_ns(ctx.devices[0], COLLECTIVES, with_async=True)
    if ns <= 0:
        return None
    return ns / 1e6 / ctx.calls
