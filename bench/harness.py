"""One run of one cell: set-up, the measured window, the check, the
result line.  ``bench/run.py`` is the command; this module is what it
runs, and what the tests drive without a chip.

A cell is found by its name in ``BENCHMARK.json``: its configuration
(``bench/configs/<config>.json``), its traffic mix
(``bench/traffic/<mix>.json``), its limits (``bench/limits/<cell>.json``)
and the readers of its per-layer metrics (``bench/metrics/<metric>.py``).
Nothing here names a cell.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS = 3.0  # profiled part of a --trace 1 window
TRACE_AT = 0.25  # ... starting this share of the window in
WAIT_AFTER = 60.0  # seconds an open loop waits for late answers


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding a cell by name
# ---------------------------------------------------------------------------


def _json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: str = ROOT


def load_cell(name, root=ROOT, rehearse=False):
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    from bench import traffic

    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = _json(os.path.join(root, conf["file"]))
    here = os.path.join(root, "bench")
    mix = _json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    traffic.validate(mix, w["traffic"])
    limits = _json(os.path.join(here, "limits", name + ".json"))
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    if rehearse:
        cfg = dict(cfg, **cfg.get("rehearsal", {}))
        mix = dict(mix, ring=min(mix["ring"], 8))
    return Cell(name, w["chips"], cfg, mix, limits, e2e, per_layer, root)


def load_reader(metric_name, root=ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def import_program():
    """``repro`` from this checkout's ``src`` (and from nowhere else)."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ImportError(f"the program is not in this checkout ({src})")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro

    where = [os.path.abspath(p) for p in repro.__path__]
    if where != [os.path.join(src, "repro")]:
        raise ImportError(f"repro imported from {where}, not {src}")
    return repro


def shortlist_width(cfg, M):
    return M if cfg["shortlist"] == "all" else min(cfg["shortlist"], M)


class RingEntry:
    """One distinct request of the ring: its ``RerankRequest``, where its
    inputs sit in the ring (``r``, mask index ``m``), and its k."""

    __slots__ = ("request", "r", "m", "k", "least_step")

    def __init__(self, request, r, m, k, least_step=0.0):
        self.request, self.r, self.m, self.k = request, r, m, k
        self.least_step = least_step

    def record(self, i, due):
        from bench.traffic import Request

        return Request(i, self, due, self.least_step)


@dataclasses.dataclass
class Setup:
    rr: object
    ring: list
    feats: object
    scores: tuple
    masks: tuple
    C: int
    devices: list


def build(cell, seed):
    """Inputs from the seed, on the device, and the served ``Reranker``."""
    import jax
    from jax.sharding import AxisType

    from bench import data, roofline, traffic
    from repro.serving import (DPPRerankConfig, Reranker, RerankRequest,
                               RouterConfig)

    cfg, mix = cell.cfg, cell.mix
    devices = jax.devices()[:cell.chips]
    kind = devices[0].device_kind
    mesh = None
    if mix.get("mesh"):
        mesh = jax.make_mesh((cell.chips,), ("data",),
                             axis_types=(AxisType.Auto,), devices=devices)
    M = cfg["pool"]
    C = shortlist_width(cfg, M)
    B, R = mix["users_per_call"], mix["ring"]
    rng = np.random.default_rng(seed)
    ks = traffic.slate_sizes(cfg, mix, R, rng)
    masked = traffic.masked_entries(cfg, mix, R)
    feats, scores, masks = data.make_inputs(
        seed, M, cfg["dim"], B, R, n_masked=len(masked),
        mask_share=cfg.get("mask_share", 0.0), mesh=mesh,
        score_dist=cfg["score_dist"])
    k_max = cfg["slate_max"]
    dpp = dict(slate_size=k_max, shortlist=C, alpha=cfg["alpha"],
               eps=cfg["eps"], window=cfg.get("window"))
    if mesh is not None:
        dpp.update(mesh=mesh, tile_m=cfg.get("mesh_tile_m"))
    else:
        dpp.update(use_kernel=True)
    router = None
    if mix["entry"] == "submit":
        router = RouterConfig(max_candidates=C, max_slate=k_max)
    rr = Reranker(DPPRerankConfig(**dpp), router_config=router)
    ring = []
    for r in range(R):
        m = masked.index(r) if r in masked else None
        k = int(ks[r])
        req = RerankRequest(scores=scores[r], feats=feats,
                            mask=None if m is None else masks[m],
                            slate_size=k)
        least = 0.0  # per selection, for a traced open loop
        if router is not None and kind in roofline.PEAKS:
            least = roofline.least_seconds(kind, 1, C, cfg["dim"], k,
                                           cfg.get("window"))[0] / k
        ring.append(RingEntry(req, r, m, k, least))
    return Setup(rr, ring, feats, scores, masks, C, devices)


def barrier(devices):
    """Wait until every op already queued on ``devices`` has run: a tiny
    program on each runs after them."""
    import jax
    import jax.numpy as jnp

    for d in devices:
        jax.block_until_ready(jax.device_put(jnp.zeros(()), d) + 1)


# ---------------------------------------------------------------------------
# The traced part of the window
# ---------------------------------------------------------------------------


class Probe:
    """Starts the profiler ``at`` seconds into the window and stops it
    ``TRACE_SECONDS`` later, at a point between two pumps or calls,
    with the devices drained on both sides."""

    def __init__(self, setup, at, seconds, registry, log_dir):
        self.setup, self.at, self.until = setup, at, at + seconds
        self.registry = registry
        self.log_dir = log_dir
        self.marks = []  # the progress count at start and at stop
        self.counters = []
        self.steps = None  # an open loop's per-pump least seconds
        self._ann = None

    def __call__(self, now, progress):
        if isinstance(progress, list):
            self.steps = progress
        if len(self.marks) == 0 and now >= self.at:
            self._start(progress)
        elif len(self.marks) == 1 and now >= self.until:
            self._stop(progress)

    def _progress(self, progress):
        return len(progress) if isinstance(progress, list) else progress

    def _start(self, progress):
        import jax

        barrier(self.setup.devices)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.counters.append(self.registry.snapshot()["counters"])
        self.marks.append(self._progress(progress))

    def _stop(self, progress):
        import jax

        barrier(self.setup.devices)
        self.marks.append(self._progress(progress))
        self.counters.append(self.registry.snapshot()["counters"])
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @property
    def done(self):
        return len(self.marks) == 2


def no_probe(now, progress):
    pass


class Ctx:
    """What a per-layer reader reads: the reduced trace (with the
    program's spans), the program's counter deltas over the traced
    window, the served calls and the greedy's least time in it (per
    chip)."""

    def __init__(self, trace, counters, calls, least_s, devices):
        self.trace, self.counters = trace, counters
        self.calls, self.least_s, self.devices = calls, least_s, devices

    def span_s(self, name):
        """The durations, in seconds, of the host spans ``name`` that lie
        inside the window."""
        return [d / 1e9 for d in self.trace.spans(name)]

    def counter(self, name, **labels):
        """The delta of a counter over the window, summed over the
        label sets that carry ``labels``."""
        tot = 0.0
        for key, v in self.counters.get(name, {}).items():
            kv = dict(p.split("=", 1) for p in key.split(",") if p)
            if all(kv.get(a) == str(b) for a, b in labels.items()):
                tot += v
        return tot

    def kernel_s(self, pattern):
        """Device seconds of the ops matching ``pattern``, averaged over
        the cell's chips."""
        return sum(self.trace.op_ns(d, pattern) for d in self.devices) \
            / len(self.devices) / 1e9

    def busy_share(self):
        return sum(self.trace.busy_ns(d) for d in self.devices) \
            / len(self.devices) / self.trace.window_ns


def _counter_delta(before, after):
    out = {}
    for name, series in after.items():
        b = before.get(name, {})
        out[name] = {k: v - b.get(k, 0.0) for k, v in series.items()}
    return out


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def device_info(devices, chips):
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def check_devices(cell):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")


def percentile(values, q):
    """The ``q``-th percentile by nearest rank (a sample's own value)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q / 100 * v.size) - 1)])


def run(cell, seed, seconds, trace, t_start, rehearse=False, log=None,
        keep_trace=None):
    """One run of ``cell``.  Returns the result line as a dict (``checks``
    last).  ``t_start`` is the process's start on the perf_counter
    clock; set-up runs from there to the window's start."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    import_program()
    if not rehearse:
        check_devices(cell)
    from repro import obs

    from bench import check, traffic

    obs.disable()
    session = obs.enable(obs.ObsConfig(
        enabled=True, trace=bool(trace), jax_annotations=bool(trace)))
    s = build(cell, seed)
    mix = cell.mix
    warm(s, mix)
    barrier(s.devices)  # the trace's drain compiles nothing in the window
    session.compile_monitor.mark()
    probe, log_dir = no_probe, None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        probe = Probe(s, TRACE_AT * seconds,
                      min(TRACE_SECONDS, seconds * 0.5),
                      session.registry, log_dir)
    setup_s = time.perf_counter() - t_start
    if mix["loop"] == "open":
        rng = np.random.default_rng([seed, 1])
        due = traffic.poisson_due(mix["rate_per_s"], seconds, rng,
                                  mix.get("burst", 1))
        recs, t_end = traffic.open_loop(
            s.rr.router, s.rr.submit, s.ring, due, seconds, probe,
            WAIT_AFTER)
        result = open_metrics(recs, t_end, seconds)
        outputs = [(rec.entry, rec.out) for rec in recs
                   if rec.out is not None]
        calls = None
    else:
        def call(entry):
            ids, gains = s.rr.rerank(entry.request)
            return np.asarray(ids), np.asarray(gains)

        outs, calls, t_end = traffic.closed_loop(call, s.ring, seconds,
                                                 probe)
        B = mix["users_per_call"]
        result = {"attempted": calls * B, "failed": 0,
                  "metrics": {"slates_per_s": calls * B / t_end}}
        outputs = [(s.ring[r], (ids, gains)) for r, ids, gains in outs]
    compiles = int(session.compile_monitor.since_mark())
    log(f"window: {t_end:.3f} s, compiles in window: {compiles}")
    device = device_info(s.devices, cell.chips)
    metrics = {}
    breakdown = None
    if trace:
        if not probe.done:
            raise RuntimeError("the window ended before the traced part")
        metrics, device_extra, breakdown = reduce_trace(
            cell, s, probe, session, calls, log_dir, keep_trace)
        device.update(device_extra)
        shutil.rmtree(log_dir, ignore_errors=True)
    else:
        metrics = result["metrics"]
        metrics["setup_s"] = setup_s
        # an end-to-end metric ``q.group`` reports the quantity ``q``: a
        # group of cells gets a bound of its own
        metrics = {m["name"]: {"value": metrics[m["name"].split(".")[0]],
                               "unit": m["unit"]} for m in cell.end_to_end}
    # the reference, after the window, with the program's state freed
    host = host_inputs(s)
    modes, interpreted = dispatch_counts(session.registry)
    unanswered = result.pop("unanswered", 0)
    del s
    checks = check.judge(cell, host, outputs, modes, interpreted,
                         unanswered, rehearse=rehearse, log=log)
    line = {"correct": all(c["value"] <= c["limit"]
                           for c in checks.values()),
            "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    obs.disable()
    return line


def warm(s, mix):
    """Run every shape the window uses: every ring entry's kind (masked
    or not, and its k outside the router) and, through the router,
    every slot."""
    if mix["entry"] == "submit":
        kinds = {}
        for e in s.ring:
            kinds.setdefault(e.m is None, []).append(e)
        slots = s.rr.router.rcfg.slots
        for entries in kinds.values():
            for e in (entries * (3 * slots))[:3 * slots]:
                s.rr.submit(e.request)
        s.rr.router.drain()
    else:
        kinds = {}
        for e in s.ring:
            kinds.setdefault((e.m is None, e.k), []).append(e)
        for entries in kinds.values():
            for e in entries[:2]:
                ids, gains = s.rr.rerank(e.request)
                np.asarray(ids), np.asarray(gains)


def open_metrics(recs, t_end, seconds):
    """Tails over every request due in the window, from its due time;
    a refused or unfinished request counts as failed and waits until the
    run stopped waiting.  ``slates_per_s``: slates finished inside the
    window, over its length (the measure of a cell offered more than
    the router sustains)."""
    ttfc, slate = [], []
    failed = unanswered = 0
    for rec in recs:
        if rec.done is None:
            failed += 1
            unanswered += not rec.refused
            ttfc.append(t_end - rec.due)
            slate.append(t_end - rec.due)
        else:
            ttfc.append(rec.first - rec.due)
            slate.append(rec.done - rec.due)
    late = [rec.sent - rec.due for rec in recs]
    print(f"open loop: {len(recs)} requests due, {failed} failed, sender "
          f"late p50 {1e3 * percentile(late, 50):.3f} ms p95 "
          f"{1e3 * percentile(late, 95):.3f} ms; ttfc p50 "
          f"{1e3 * percentile(ttfc, 50):.3f} ms slate p50 "
          f"{1e3 * percentile(slate, 50):.3f} ms", file=sys.stderr)
    return {"attempted": len(recs), "failed": failed,
            "unanswered": unanswered,
            "metrics": {"ttfc_p95_ms": 1e3 * percentile(ttfc, 95),
                        "slate_p95_ms": 1e3 * percentile(slate, 95),
                        "slates_per_s": sum(
                            1 for rec in recs if rec.done is not None
                            and rec.done <= seconds) / seconds}}


def dispatch_counts(registry):
    """``({mode: n}, interpreted n)`` from the program's dispatch
    counters."""
    counters = registry.snapshot()["counters"]
    modes = {}
    for key, n in counters.get("dpp_kernel_dispatch_total", {}).items():
        labels = dict(kv.split("=", 1) for kv in key.split(","))
        modes[labels["mode"]] = modes.get(labels["mode"], 0) + int(n)
    interpreted = int(sum(
        counters.get("dpp_kernel_interpreted_total", {}).values()))
    return modes, interpreted


def host_inputs(s):
    """The inputs on the host, for the reference."""
    return {"feats": np.asarray(s.feats),
            "scores": [np.asarray(x) for x in s.scores],
            "masks": [np.asarray(x) for x in s.masks], "C": s.C}


def reduce_trace(cell, s, probe, session, calls, log_dir, keep_trace):
    """The per-layer metrics, ``busy_s``/``window_s`` and the breakdown
    from the traced window."""
    from bench import roofline, xplane

    path = xplane.find_trace(log_dir)
    if keep_trace:
        os.makedirs(keep_trace, exist_ok=True)
        shutil.copy(path, os.path.join(keep_trace, os.path.basename(path)))
    tr = xplane.Trace.load(path)
    devs = [d.id for d in s.devices]
    a, b = probe.marks
    cfg, mix = cell.cfg, cell.mix
    kind = s.devices[0].device_kind
    if kind not in roofline.PEAKS:  # a rehearsal: no device numbers
        n_calls, least = b - a, None
    elif calls is None:  # open loop: the selections of the chunks
        n_calls = None  # launched in the window, delivered a pump later
        least = float(sum(probe.steps[a + 1:b + 1]))
    else:
        n_calls = b - a
        least = n_calls * roofline.least_seconds(
            kind, mix["users_per_call"],
            s.C // cell.chips, cfg["dim"], cfg["slate_max"],
            cfg.get("window"))[0]
    ctx = Ctx(tr, _counter_delta(*probe.counters), n_calls, least, devs)
    metrics = {}
    for m in cell.per_layer:
        v = load_reader(m["name"], cell.root)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = ctx.busy_share() * tr.window_ns / 1e9
    breakdown = {"device_ops": tr.top_ops(devs[0]),
                 "idle_gaps": tr.idle_gaps(devs[0])}
    return metrics, {"busy_s": busy, "window_s": tr.window_ns / 1e9}, \
        breakdown


def enable_compile_cache():
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path, every compile cached however short.  Call before the
    first compile; the command also points ``JAX_COMPILATION_CACHE_DIR``
    there before JAX is imported."""
    import jax

    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
