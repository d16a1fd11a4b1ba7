"""Each cell rehearsed end to end at a tiny size on the CPU, and a cell,
mix and metric added by files alone."""
import json
import os
import shutil
import time

import pytest

from bench import harness
from bench.tests.conftest import ROOT, all_cells

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


def rehearse(name, trace=0, root=ROOT, seed=3_000_000_019):
    cell = harness.load_cell(name, root=root, rehearse=True)
    return harness.run(cell, seed, 1.0, trace, time.perf_counter(),
                       rehearse=True, log=lambda *a: None)


@pytest.mark.parametrize("name", all_cells())
def test_cell_rehearses_correct(name, held_root):
    line = rehearse(name, root=held_root)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0
    assert list(line)[-1] == "checks"
    cell = harness.load_cell(name, root=held_root)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert line["metrics"]["setup_s"]["value"] > 0


def test_no_chip_means_no_result():
    cell = harness.load_cell(CELLS[0])
    with pytest.raises(harness.NoChip):
        harness.run(cell, 1, 1.0, 0, time.perf_counter())


def test_a_cell_added_by_files_alone(tmp_path):
    """A configuration, a mix, limits and a per-layer metric, each a new
    file, and entries in BENCHMARK.json: no existing file changes."""
    root = tmp_path
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/feed1k.json")))
    cfg.update(pool=4096, shortlist=200)
    (root / "bench/configs/feedsmall.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/closed-b2.json").write_text(json.dumps(
        {"loop": "closed", "entry": "rerank", "users_per_call": 2,
         "slate": "max", "masked": True, "ring": 3}))
    (root / "bench/limits/feedsmall.closed-b2.json").write_text(
        json.dumps({"pick_gap": 1e-3, "gain_err": 1e-3}))
    (root / "bench/metrics/calls_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.calls)\n")
    bench["configs"].append({"name": "feedsmall", "source": "test",
                             "file": "bench/configs/feedsmall.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "feedsmall.closed-b2",
                               "config": "feedsmall",
                               "traffic": "closed-b2", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "calls_traced", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "slates_per_s",
                               "workloads": ["feedsmall.closed-b2"]})
    for m in bench["end_to_end"]:
        if m["name"] == "slates_per_s":
            m["workloads"].append("feedsmall.closed-b2")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("feedsmall.closed-b2", root=str(root))
    assert "calls_traced" in [m["name"] for m in cell.per_layer]
    line = rehearse("feedsmall.closed-b2", trace=1, root=str(root))
    assert line["correct"], line["checks"]
    assert line["metrics"]["calls_traced"]["value"] > 0
    assert line["device"]["window_s"] > 0


def test_bursts_keep_the_mean_rate_and_the_work_of_every_seed():
    import numpy as np

    from bench import traffic

    runs = [traffic.poisson_due(40.0, 30.0, np.random.default_rng(s), 8)
            for s in (1, 2**31 + 5)]
    for due in runs:
        assert due.size == 40 * 30 and np.all(np.diff(due) >= 0)
        assert due[0] == 0.0 and due[-1] < 30.0
        assert np.all(due.reshape(-1, 8) == due[::8, None])
    gaps = [np.sort(np.diff(np.append(due[::8], 30.0))) for due in runs]
    assert np.allclose(gaps[0], gaps[1])
    assert not np.allclose(runs[0], runs[1])


def test_an_arrival_process_added_by_files_alone(tmp_path, held_root):
    """A mix with another arrival process (bursts of 4 due at once) and
    its cell, by new files and entries alone; an open loop reports
    ``slates_per_s`` too where the cell lists it."""
    root = tmp_path / "root"
    shutil.copytree(held_root, root)
    bench = json.load(open(root / "BENCHMARK.json"))
    mix = json.load(open(root / "bench/traffic/router-steady.json"))
    mix.update(burst=4, rate_per_s=16.0)
    (root / "bench/traffic/router-burst4.json").write_text(json.dumps(mix))
    (root / "bench/limits/feed1k.router-burst4.json").write_text(
        (root / "bench/limits/feed1k.router-steady.json").read_text())
    bench["workloads"].append({"name": "feed1k.router-burst4",
                               "config": "feed1k", "traffic": "router-burst4",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "feed1k.router-steady" in m.get("workloads", []) \
                or m["name"] == "slates_per_s":
            m["workloads"].append("feed1k.router-burst4")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = rehearse("feed1k.router-burst4", root=str(root))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"ttfc_p95_ms", "slate_p95_ms",
                                    "slates_per_s", "setup_s"}
    assert line["metrics"]["slates_per_s"]["value"] > 0


def _command(root, *extra):
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_the_command_without_a_chip_exits_2_and_prints_nothing():
    p = _command(ROOT)
    assert p.returncode == 2 and p.stdout == ""


def test_the_command_without_the_program_fails_and_prints_nothing(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _command(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""
