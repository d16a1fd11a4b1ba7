"""How ``correct`` is decided: every slate the window produced, replayed
through the float64 reference (``bench.reference.follow``).

The numbers compared, each against its limit:

* ``pick_gap``: the largest relative shortfall of a pick's gain below
  the best gain on offer, over every step of every slate (limit in
  ``bench/limits/<cell>.json``, set from the program's sound runs and
  the control's, see PERF.md);
* ``gain_err``: the largest relative error of a served gain (the same);
* ``invalid``: slates with a structural fault: a wrong length, an id
  outside the shortlist or masked, a repeated id, a -1 before a pick
  (limit 0);
* ``unanswered``: requests accepted that never finished (limit 0);
* ``jnp_dispatch`` and ``interpreted``: greedy dispatches that took the
  jnp path or ran a Pallas kernel in the interpreter (limit 0).

A slate served many times (the window cycles through a ring of
requests) is replayed once per distinct answer, the (request, user)
groups on a few threads.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import reference


def _users(ids, gains):
    ids, gains = np.asarray(ids), np.asarray(gains)
    if ids.ndim == 1:
        return [(None, ids, gains)]
    return [(u, ids[u], gains[u]) for u in range(ids.shape[0])]


def _group(cfg, host, r, u, entry, answers):
    """Replay the distinct answers of one (ring entry, user)."""
    scores = host["scores"][r]
    scores = scores if u is None else scores[u]
    mask = None if entry.m is None else host["masks"][entry.m]
    feats = host["feats"]
    sl = reference.shortlist(scores, host["C"], mask)
    cols = (sl, reference.kernel_columns(feats[sl], scores[sl],
                                         cfg["alpha"]))
    out = []
    for i, g, n in answers.values():
        out.append((n, reference.follow(
            feats, scores, host["C"], i, g, cfg["alpha"], cfg["eps"],
            cfg.get("window"), mask, columns=cols)))
    return out


def follow_all(cell, host, outputs):
    """``(pick_gap, gain_err, invalid, slates, distinct)`` over
    ``outputs``, a list of ``(ring entry, (ids, gains))``."""
    groups = {}
    invalid = 0
    for entry, (ids, gains) in outputs:
        for u, i, g in _users(ids, gains):
            if i.shape != (entry.k,) or g.shape != (entry.k,):
                invalid += 1
                continue
            groups.setdefault((entry.r, u), (entry, {}))[1].setdefault(
                (i.tobytes(), g.tobytes()), [i, g, 0])[2] += 1
    keys = list(groups)
    pick_gap = gain_err = 0.0
    slates = distinct = 0
    workers = max(1, min(len(keys), os.cpu_count() or 1, 8))
    with ThreadPoolExecutor(workers) as ex:
        futs = [ex.submit(_group, cell.cfg, host, r, u, *groups[(r, u)])
                for r, u in keys]
        for fut in futs:
            for n_served, f in fut.result():
                slates += n_served
                distinct += 1
                if f.invalid is not None:
                    invalid += n_served
                    continue
                pick_gap = max(pick_gap, f.pick_gap)
                gain_err = max(gain_err, f.gain_err)
    return pick_gap, gain_err, invalid, slates, distinct


def judge(cell, host, outputs, modes, interpreted, unanswered,
          rehearse=False, log=print):
    """The checks of one run, each ``{"value", "limit"}``."""
    pick_gap, gain_err, invalid, slates, distinct = follow_all(
        cell, host, outputs)
    log(f"compared {slates} slates ({distinct} distinct answers) with the "
        f"float64 reference; dispatch modes {modes}, interpreted "
        f"{interpreted}")
    if slates == 0:
        invalid += 1  # nothing was served: nothing can be correct
    lim = cell.limits
    checks = {
        "pick_gap": {"value": float(pick_gap), "limit": lim["pick_gap"]},
        "gain_err": {"value": float(gain_err), "limit": lim["gain_err"]},
        "invalid": {"value": invalid, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "jnp_dispatch": {"value": modes.get("jnp", 0), "limit": 0},
    }
    if not rehearse:  # a CPU rehearsal interprets every kernel
        checks["interpreted"] = {"value": interpreted, "limit": 0}
    return checks
