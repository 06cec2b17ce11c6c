"""BENCHMARK.json and the files it names: every cell resolves its
configuration, traffic and metric files by name, names and units keep to
their character sets, and each per-layer metric moves an end-to-end metric
that its cells report.  A new cell is new files only."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.BENCH
ROOT = spec.ROOT


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_resolves_by_name(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for wl in bench["workloads"]:
        cell = spec.resolve(wl["name"])
        assert cell["workload"]["config"] == wl["config"]
        assert cell["workload"]["traffic"] == wl["traffic"]
        assert cell["workload"]["chips"] == wl["chips"] in (1, 4)
        assert cell["workload"]["why"] == wl["why"]
        assert configs[wl["config"]]["file"] == \
            f"bench/configs/{wl['config']}.json"
        assert (BENCH / "drivers" / f"{cell['traffic']['kind']}.py").is_file()
        for group in ("end_to_end", "per_layer"):
            for m in spec.cell_metrics(bench, wl["name"], group):
                assert callable(spec.metric_reader(m["name"]))


def test_config_files_match_their_entries(bench):
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert k in conf["published"] and conf[k] != conf["published"][k]


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_names_and_units(bench, group):
    names = [m["name"] for m in bench[group]]
    assert len(names) == len(set(names))
    for m in bench[group]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if group == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert set(m) <= {"name", "unit", "better", "bound", "source",
                              "workloads"}
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert set(m) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    for wl in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            assert NAME.match(wl[key]), wl[key]
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])


def test_per_layer_moves_a_metric_its_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in target or cell in target["workloads"]


def test_every_cell_reports_setup_another_end_to_end_and_a_layer(bench):
    for wl in bench["workloads"]:
        e2e = [m["name"] for m in
               spec.cell_metrics(bench, wl["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.cell_metrics(bench, wl["name"], "per_layer")


def test_a_new_cell_is_new_files_only(tmp_path, bench):
    """Copy the benchmark, add a configuration, a mix, a cell and a metric
    as new files and one entry each, and resolve the new cell."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    b = json.loads(json.dumps(bench))
    conf = json.loads((BENCH / "configs" / "granite-3-2b.json").read_text())
    conf["name"] = "granite-3-2b-copy"
    (root / "bench" / "configs" / "granite-3-2b-copy.json").write_text(
        json.dumps(conf))
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix.update(rate_rps=1.3)
    (root / "bench" / "traffic" / "chat-overload.json").write_text(
        json.dumps(mix))
    cell = {"name": "granite-3-2b-copy.serve-overload",
            "config": "granite-3-2b-copy", "traffic": "chat-overload",
            "chips": 1, "why": "the chat mix at 1.3 times the knee"}
    (root / "bench" / "workloads" / f"{cell['name']}.json").write_text(
        json.dumps(dict(cell, limits={"logit_gap": 1.0})))
    (root / "bench" / "metrics" / "requests_due.serve.py").write_text(
        "def read(rec):\n    return len(rec.requests) or None\n")
    b["workloads"].append(cell)
    b["per_layer"].append({"name": "requests_due.serve", "unit": "1",
                           "better": "higher", "source": "host_clock",
                           "layer": "serve scheduler",
                           "moves": "ttft_p50_s",
                           "workloads": [cell["name"]]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    code = (
        "import sys; sys.path.insert(0, 'bench'); import spec, json;"
        "c = spec.resolve('granite-3-2b-copy.serve-overload');"
        "b = spec.benchmark();"
        "ms = [m['name'] for m in spec.cell_metrics(b, c['name'], 'per_layer')];"
        "r = spec.metric_reader('requests_due.serve');"
        "print(json.dumps([c['config']['name'], c['traffic']['rate_rps'], ms]))")
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    name, rate, ms = json.loads(out.stdout)
    assert name == "granite-3-2b-copy" and rate == 1.3
    assert "requests_due.serve" in ms and "queue_wait_p50_s.serve" not in ms


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_run_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-1.6b.train-s4096", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=120)
    assert out.returncode == 3
    assert "no TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_run_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no program to
    measure: the run fails and prints no result."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-3-2b.serve-chat", "--seed", "3", "--seconds", "1"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
