"""The harness end to end on the CPU, without its look for a chip: sound
runs of every cell are correct, and the control and each planted fault
in the grouped sum turn ``correct`` false. Also the benchmark's file
against the contract's shape, and the entry point without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import control, harness, reference

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
ROWS = 2400


def run(cell, kind=None, seed=2**31 + 3):
    return control.read(cell, seed, 0.2, kind, rows=ROWS,
                        require_tpu=False)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = run(cell)
    assert r["correct"] and r["attempted"] >= 1 and r["failed"] == 0
    assert r["checks"] == {"wrong_answers": 0, "max_abs_gap": 0}


@pytest.mark.parametrize("kind", ("bf16",) + control.FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_control_and_faults_fail_the_check(cell, kind):
    r = run(cell, kind)
    assert not r["correct"]
    assert r["checks"]["wrong_answers"] >= 1


def test_result_line_shape():
    r = harness.run_cell(CELLS[0], 5, 0.2, False, t_process=time.monotonic(),
                         rows=ROWS, require_tpu=False)
    assert list(r)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(r)
    assert set(r["metrics"]) == {m["name"] for m in
                                 harness.metrics_of(BENCH, CELLS[0], False)}
    assert all(v["value"] > 0 for v in r["metrics"].values())


def test_no_tpu_is_refused():
    with pytest.raises(harness.NoAccelerator):
        harness.run_cell(CELLS[0], 5, 0.2, False, t_process=0.0, rows=ROWS)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, *BENCH["command"][1:],
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_file_is_data_driven():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        conf = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(conf["reduced"])
        for kind in ("datasets", "deployments"):
            key = kind[:-1]
            assert (ROOT / "bench" / kind / f"{conf[key]}.py").is_file()
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert any((ROOT / "bench" / "traffic" / f"{w['traffic']}{ext}")
                   .is_file() for ext in (".json", ".py"))
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"]


PY_MIX = """
class Count:
    name = "count_by_vendor"
    nkeys = 1

    def __init__(self, schema):
        self.i = [n for n, _ in schema].index("VendorID")

    def build(self, table):
        from repro.sql import col, count_
        return table().groupBy(col("VendorID")).agg(count_().alias("n"))

    def reference(self, rows):
        out = {}
        for r in rows:
            out[int(r[self.i])] = out.get(int(r[self.i]), 0) + 1
        return sorted(out.items())

    def sum_bytes(self, rows, parts):
        return None


def mix(dataset):
    return {"order": "alternate", "queries": [Count(dataset.SCHEMA)]}
"""


def test_a_mix_in_python_is_found_by_name(tmp_path):
    from repro.core import FlintConfig, FlintContext

    (tmp_path / "by-vendor.py").write_text(PY_MIX)
    ds = harness.dataset("tlc-yellow-2015")
    mix = harness.load_mix("by-vendor", ds, traffic_dir=tmp_path)
    (query,) = mix["queries"]
    raw = ds.generate(500, 9)
    ctx = FlintContext(config=FlintConfig(concurrency=4))
    ctx.upload(ds.TABLE, raw)
    got = query.build(lambda: ctx.read_csv(ds.TABLE, list(ds.SCHEMA), 4))
    assert sorted(got.collect()) == query.reference(reference.parse(raw))
    with pytest.raises(FileNotFoundError):
        harness.load_mix("absent", ds, traffic_dir=tmp_path)
