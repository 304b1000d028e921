"""BENCHMARK.json and the files it names; a cell, a mix and a metric
are added as new files and entries, with no edit under bench/."""
import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_keys_and_names():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    for w in cells.values():
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", cells)


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      BENCHMARK["workloads"]])
def test_every_cell_loads_with_its_readers(workload):
    cell = spec.load(ROOT, workload)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    assert set(spec.readers(cell)) == {m["name"] for m in cell.per_layer}
    e = cell.engine
    longest = max(cell.traffic.get("system_prompts", {}).get("tokens", 0)
                  + cell.traffic["prompt_tokens"]["max"]
                  + cell.traffic["output_tokens"]["max"], 0)
    assert longest <= e["max_seq_len"]
    # the longest request fits the pool beside the null page, and every
    # lane the engine runs holds the clients of a closed loop
    assert e["num_blocks"] > e["max_seq_len"] // e["block_size"] + 1
    assert cell.load.get("clients", 0) <= e["max_running"] \
        == e["decode_batch"]
    for c in BENCHMARK["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_a_cell_is_added_by_files_and_entries_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["workloads"].append({"name": "stablelm_1_6b_exact.dummy",
                               "config": "stablelm_1_6b_exact",
                               "traffic": "dummy", "chips": 1,
                               "why": "a dummy cell"})
    bench["per_layer"].append({"name": "dummy_count.dummy", "unit": "n",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler", "moves": "setup_s",
                               "workloads": ["stablelm_1_6b_exact.dummy"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = {"loop": "open", "system_prompts": {"count": 2, "tokens": 64},
           "prompt_tokens": {"dist": "lognormal", "median": 100,
                             "sigma": 0.5, "min": 16, "max": 400},
           "output_tokens": {"dist": "uniform", "min": 16, "max": 64},
           "sampling": {"temperature": 0.7, "top_k": 50, "greedy_every": 4},
           "why": "dummy"}
    (tmp_path / "bench" / "traffic" / "dummy.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "cells" / "stablelm_1_6b_exact.dummy.json"
     ).write_text(
        json.dumps({"rate_per_s": 1.0, "prime_s": 1, "tail_s": 1,
                    "drain_s": 1, "check": {"tokens": 10, "limits": {}}}))
    (tmp_path / "bench" / "metrics" / "dummy_count.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file() and "dummy" not in p.name}
    cell = spec.load(tmp_path, "stablelm_1_6b_exact.dummy")
    assert cell.traffic["why"] == "dummy" and cell.load["rate_per_s"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["dummy_count.dummy"]
    assert spec.readers(cell)["dummy_count.dummy"](None) == 7.0
    assert all(p.read_bytes() == b for p, b in before.items())
    with pytest.raises(spec.SpecError):
        spec.load(tmp_path, "no_such.cell")


def test_run_fails_without_a_tpu():
    r = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", str(2 ** 31 + 7),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0
    assert r.stdout.strip() == "" or not r.stdout.strip().splitlines()[
        -1].startswith("{")
    assert "no TPU" in r.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCHMARK["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode != 0 and "{" not in r.stdout
