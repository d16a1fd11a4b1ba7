"""The benchmark's own tests run on the CPU, at tiny sizes, with four
virtual devices for the sharded cell:

    python -m pytest bench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

# Cells built and measured but held out of BENCHMARK.json (PERF.md,
# Open questions): their files are in bench/, and these are the entries
# a later PR adds to bring them back.  The tests run them all.
HELD_OUT = [
    {"workload": {"name": "feed1k.router-steady", "config": "feed1k",
                  "traffic": "router-steady", "chips": 1, "why": "router"},
     "end_to_end": [
         {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
          "source": "host_clock", "workloads": ["feed1k.router-steady"]}
         for n in ("ttfc_p95_ms", "slate_p95_ms")],
     "per_layer": [
         {"name": n, "unit": u, "better": b, "source": s, "layer": layer,
          "moves": moves, "workloads": ["feed1k.router-steady"]}
         for n, u, b, s, layer, moves in (
             ("router.admit_ms", "ms", "lower", "program_span", "router",
              "ttfc_p95_ms"),
             ("router.pump_ms", "ms", "lower", "program_span", "router",
              "slate_p95_ms"),
             ("greedy_roofline.router", "%", "higher", "device_trace",
              "kernels", "slate_p95_ms"),
             ("device_idle.router", "%", "lower", "device_trace", "device",
              "slate_p95_ms"))]},
    {"workload": {"name": "pool1m.sharded-4chip", "config": "pool1m",
                  "traffic": "sharded-4chip", "chips": 4, "why": "mesh"},
     "end_to_end": [], "per_layer": [
         {"name": "sharded.collective_ms", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "sharded",
          "moves": "slates_per_s", "workloads": ["pool1m.sharded-4chip"]}]},
]


def _bench():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def all_cells():
    """The cells of BENCHMARK.json and the held-out ones."""
    names = [w["name"] for w in _bench()["workloads"]]
    return names + [h["workload"]["name"] for h in HELD_OUT
                    if h["workload"]["name"] not in names]


@pytest.fixture(scope="session")
def held_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json also lists the held-out cells."""
    root = tmp_path_factory.mktemp("held-root")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = _bench()
    names = {w["name"] for w in bench["workloads"]}
    for h in HELD_OUT:
        name = h["workload"]["name"]
        if name in names:
            continue
        bench["workloads"].append(h["workload"])
        bench["end_to_end"] += h["end_to_end"]
        bench["per_layer"] += h["per_layer"]
        if h["workload"]["traffic"] != "router-steady":
            for m in bench["end_to_end"] + bench["per_layer"]:
                if "slates_per_s" in (m["name"], m.get("moves")) \
                        and name not in m["workloads"]:
                    m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)
