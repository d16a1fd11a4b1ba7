"""sharded.merge_us: device microseconds per cross-chip merge of the
mesh greedy.  The ops of the merges' own kinds, all-gather and
all-reduce (synchronous or async), on the first chip of the mesh in the
traced window, over the merges the program counted in it
(``sharded_merges_total``, every kind: each step's argmax, winner
broadcast and, windowed, window gather).  An op is matched by its HLO
opcode, not its name: JAX names a ``psum`` all-reduce ``psum.N``.
Other collectives, such as a reshard of V between chips, are left out.
None without the counter."""

MERGES = r" (all-gather|all-reduce)(-start|-done)?\("


def read(ctx):
    merges = ctx.counter("sharded_merges_total")
    if merges <= 0:
        return None
    ns = ctx.trace.op_ns(ctx.devices[0], MERGES, with_async=True)
    if ns <= 0:
        return None
    return ns / 1e3 / merges
