"""``correct`` comes out false when the timed path is broken underneath
(each fault a cell can have), and when the control stands in for the
program.  The runs skip the look for a chip and are otherwise whole.
"""
import functools
import json
import os
import time

import jax.numpy as jnp
import pytest

from bench import control, harness
from bench.tests.conftest import HELD_OUT, ROOT, all_cells

ROUTER = "feed1k.router-steady"
CLOSED = [n for n in all_cells() if n != ROUTER]
SHARDED = [n for n in CLOSED if "sharded" in n]


def users_per_call(name):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    traffic = {w["name"]: w["traffic"] for w in bench["workloads"]}
    traffic.update({h["workload"]["name"]: h["workload"]["traffic"]
                    for h in HELD_OUT})
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                      traffic[name] + ".json")))
    return mix["users_per_call"]


# half of a call's batch can only be left out where a call has several
BATCHED = [n for n in CLOSED if users_per_call(n) > 1]


def run(name, root):
    cell = harness.load_cell(name, root=root, rehearse=True)
    return harness.run(cell, 424242, 1.0, 0, time.perf_counter(),
                       rehearse=True, log=lambda *a: None)


def alter_answer(ids):
    """A wrong item where the slate is produced: the second pick
    replaced by the first."""
    return ids.at[..., 1].set(ids[..., 0])


@pytest.mark.parametrize("name", CLOSED)
def test_an_answer_altered_is_caught(name, held_root, monkeypatch):
    from repro.serving import api

    for fn in ("_rerank_impl", "_sharded_rerank_impl"):
        orig = getattr(api, fn)

        @functools.wraps(orig)
        def broken(*a, _orig=orig, **k):
            ids, dh = _orig(*a, **k)
            return alter_answer(ids), dh

        monkeypatch.setattr(api, fn, broken)
    assert not run(name, held_root)["correct"]


@pytest.mark.parametrize("name", BATCHED)
def test_half_the_batch_left_out_is_caught(name, held_root, monkeypatch):
    """The second half of a call's users gets the first half's slates."""
    from repro.serving import api

    orig_b, orig_s = api._rerank_batch_impl, api._sharded_rerank_impl

    def first_half(scores, feats, mask):
        h = (scores.shape[0] + 1) // 2
        return (scores[:h], feats[:h] if feats.ndim == 3 else feats,
                mask[:h] if mask is not None and mask.ndim == 2 else mask)

    def twice(out, n):
        ids, dh = out
        return (jnp.concatenate([ids, ids])[:n],
                jnp.concatenate([dh, dh])[:n])

    def batch(scores, feats, cfg, mask):
        s, f, m = first_half(scores, feats, mask)
        return twice(orig_b(s, f, cfg, m), scores.shape[0])

    def sharded(scores, feats, cfg, mask, kern):
        s, f, m = first_half(scores, feats, mask)
        return twice(orig_s(s, f, cfg, m, kern), scores.shape[0])

    monkeypatch.setattr(api, "_rerank_batch_impl", batch)
    monkeypatch.setattr(api, "_sharded_rerank_impl", sharded)
    assert not run(name, held_root)["correct"]


@pytest.mark.parametrize("name", SHARDED)
def test_the_exchange_between_chips_left_out_is_caught(name, held_root,
                                                       monkeypatch):
    """Each chip takes its own best candidate as the global winner."""
    from repro.core import sharded

    def local(d2, ax, off, axis_name):
        jl = jnp.argmax(d2).astype(jnp.int32)
        return jl, d2[jl], jl + off, jnp.bool_(True)

    monkeypatch.setattr(sharded, "_global_argmax", local)
    sharded._greedy_fn.cache_clear()
    try:
        assert not run(name, held_root)["correct"]
    finally:
        sharded._greedy_fn.cache_clear()


def test_an_answer_altered_in_the_router_is_caught(held_root, monkeypatch):
    from repro.serving import router

    orig = router.greedy_chunk_slots

    def broken(*a, **k):
        st, sel, dh = orig(*a, **k)
        return st, jnp.where(sel >= 0, (sel + 1) % sel.shape[-1], sel), dh

    monkeypatch.setattr(router, "greedy_chunk_slots", broken)
    assert not run(ROUTER, held_root)["correct"]


def test_a_step_that_leaves_its_state_unchanged_is_caught(held_root,
                                                          monkeypatch):
    """Every chunk starts again from the state it was given."""
    from repro.serving import router

    orig = router.greedy_chunk_slots

    def stuck(spec, state, V, chunk):
        st, sel, dh = orig(spec, state, V, chunk)
        return state, sel, dh

    monkeypatch.setattr(router, "greedy_chunk_slots", stuck)
    assert not run(ROUTER, held_root)["correct"]


@pytest.mark.parametrize("name", all_cells())
def test_the_control_fails_the_limits(name, held_root):
    """The reference at bfloat16 precision in the program's place reads
    above a limit (at the rehearsal's size, as the tests can hold)."""
    cell = harness.load_cell(name, root=held_root, rehearse=True)
    gap, err, n = control.readings(cell, 5)
    assert n > 0
    assert gap > cell.limits["pick_gap"] or err > cell.limits["gain_err"]
