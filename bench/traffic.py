"""The one traffic generator: it reads a mix's data file
(``bench/traffic/<mix>.json``) and drives the program with it.

Keys of a mix file:

* ``loop``: ``"open"`` (requests arrive on a schedule, whatever the
  system does: independent users) or ``"closed"`` (one client sends its
  next call when the last one has returned).
* ``entry``: the served entry point, ``"submit"`` (the continuous-
  batching router) or ``"rerank"`` (a whole call).
* ``users_per_call``: B, the users whose slates one call returns
  (1: a single request, scores ``(M,)``).
* ``slate``: ``"range"`` (k drawn per request from the configuration's
  ``slate_min``..``slate_max``) or ``"max"`` (every k = ``slate_max``).
* ``masked``: every ``mask_every``-th request (configuration) carries a
  mask with ``mask_share`` of the pool marked seen.
* ``ring``: distinct requests made before the window; the window cycles
  through them.  Nothing on these paths caches by request.
* ``rate_per_s`` and ``arrivals`` (``"poisson"``), open loop only:
  requests due at that mean rate, in bursts of ``burst`` requests due
  at once (default 1), the bursts' gaps exponential.
* ``mesh``: shard the pool over the cell's chips (``"data"`` axis).

Every seed gets the same set of slate sizes and inter-arrival gaps, in
another order, and as many requests due in the window, so seeds change
which requests come when and not how much work a run holds.
"""
from __future__ import annotations

import time

import numpy as np

KEYS = {"loop", "entry", "users_per_call", "slate", "masked", "ring",
        "rate_per_s", "arrivals", "burst", "mesh", "why", "rate_base"}


def validate(mix, name):
    unknown = set(mix) - KEYS
    if unknown:
        raise ValueError(f"traffic {name}: unknown keys {sorted(unknown)}")
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"traffic {name}: loop must be open or closed")
    if mix["entry"] not in ("submit", "rerank"):
        raise ValueError(f"traffic {name}: entry must be submit or rerank")
    if mix["loop"] == "open" and mix.get("arrivals") != "poisson":
        raise ValueError(f"traffic {name}: an open loop needs "
                         f"arrivals=poisson and rate_per_s")
    if mix["entry"] == "submit" and mix["users_per_call"] != 1:
        raise ValueError(f"traffic {name}: the router takes single requests")
    burst = mix.get("burst", 1)
    if not (isinstance(burst, int) and burst >= 1):
        raise ValueError(f"traffic {name}: burst must be a whole number >= 1")


def slate_sizes(cfg, mix, ring, rng):
    """k for each ring entry: the same multiset for every seed."""
    if mix["slate"] == "max":
        return np.full(ring, cfg["slate_max"], np.int64)
    lo, hi = cfg["slate_min"], cfg["slate_max"]
    return lo + rng.permutation(ring) % (hi - lo + 1)


def masked_entries(cfg, mix, ring):
    """Ring entries that carry a mask: every ``mask_every``-th."""
    if not mix.get("masked"):
        return []
    e = cfg["mask_every"]
    return [r for r in range(ring) if r % e == e - 1]


def poisson_due(rate, seconds, rng, burst=1):
    """Due times in ``[0, seconds)`` of an open loop at ``rate`` requests
    per second, ``burst`` due at once.  The bursts' gaps sit at the
    exponential distribution's quantiles, scaled to fill the window
    exactly and shuffled by the seed: every seed has the same bursts and
    gaps, in another order."""
    n = max(1, round(rate * seconds / burst))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return np.repeat(due, burst)


class Request:
    """One request of the window, on the harness's clock (seconds from
    the window's start)."""

    __slots__ = ("i", "entry", "due", "sent", "first", "done", "out",
                 "refused", "delivered", "least_step")

    def __init__(self, i, entry, due, least_step):
        self.i, self.entry, self.due = i, entry, due
        self.sent = self.first = self.done = self.out = None
        self.refused = False
        self.delivered = 0
        self.least_step = least_step


def open_loop(router, submit, reqs, due, seconds, probe, wait_after=60.0):
    """Drive the router on the schedule ``due``.

    ``reqs[i % len(reqs)]`` is sent at ``due[i]``; the client holds its
    first chunk at the end of the pump that delivers it, and its slate
    at the end of the pump that finishes it.  After the window, pumps
    until every request sent has finished, ``wait_after`` seconds at
    most.  ``probe(now, pumps)`` runs between pumps (the traced
    window).  Returns ``(records, t_end)``.
    """
    from repro.serving.router import RouterQueueFull

    R = len(reqs)
    recs, pending = [], {}
    steps = []  # selections delivered by each pump
    t0 = time.perf_counter()
    i, n = 0, len(due)
    while True:
        now = time.perf_counter() - t0
        while i < n and due[i] <= now:
            rec = reqs[i % R].record(i, due[i])
            recs.append(rec)
            rec.sent = now
            try:
                pending[submit(reqs[i % R].request)] = rec
            except RouterQueueFull:
                rec.refused = True
            i += 1
        if i >= n and (not pending or now > seconds + wait_after):
            break
        probe(now, steps)
        if not pending:
            wait = due[i] - (time.perf_counter() - t0)
            if wait > 1e-3:
                time.sleep(wait - 5e-4)
            continue
        router.pump()
        t = time.perf_counter() - t0
        got = 0.0
        for h, rec in list(pending.items()):
            d = h.delivered
            if d > rec.delivered:
                got += (d - rec.delivered) * rec.least_step
                rec.delivered = d
                if rec.first is None:
                    rec.first = t
            if h.done:
                rec.done = t
                rec.out = h.slate()
                del pending[h]
        steps.append(got)
    return recs, time.perf_counter() - t0


def closed_loop(call, ring, seconds, probe):
    """One client: the next call as soon as the last has returned, each
    ending in a host copy of its result.  ``probe(now, calls)`` runs
    between calls.  Returns ``(outputs [(ring index, ids, gains)],
    calls, t_end)``, the window ending when its last call returns."""
    R = len(ring)
    outs = []
    t0 = time.perf_counter()
    calls = 0
    while True:
        probe(time.perf_counter() - t0, calls)
        ids, gains = call(ring[calls % R])
        outs.append((calls % R, np.asarray(ids), np.asarray(gains)))
        calls += 1
        if time.perf_counter() - t0 >= seconds:
            break
    return outs, calls, time.perf_counter() - t0
