"""The harness: no result without a chip, the peaks table by device kind,
and BENCHMARK.json's cells, metrics and files found by name."""
import json
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench.lib import common, harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi35moe-skewed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT)})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def _fake_devices(monkeypatch, kind, n=1):
    import jax
    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda: [dev] * n)


def test_unknown_device_kind_is_an_error(monkeypatch):
    _fake_devices(monkeypatch, "TPU v99")
    with pytest.raises(common.BenchError, match="peaks"):
        common.device_info(1)


def test_fewer_chips_than_the_cell_asks_for_is_an_error(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite", n=1)
    with pytest.raises(common.BenchError, match="4 chips"):
        common.device_info(4)


def test_known_device_kind_gets_its_peaks(monkeypatch):
    _fake_devices(monkeypatch, "TPU v5 lite")
    info = common.device_info(1)
    assert info["peaks"]["bf16_flops"] == 197e12


def test_every_metric_has_a_reader_and_allowed_names():
    for kind in ("end_to_end", "per_layer"):
        for m in SPEC[kind]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert callable(harness.reader(m["name"]))
            for w in m.get("workloads", []):
                assert w in {c["name"] for c in SPEC["workloads"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
    assert "setup_s" in e2e
    assert all(m["bound"] <= 0.25 for m in SPEC["end_to_end"])


def test_every_cell_finds_its_configuration_and_traffic():
    names = set()
    for cell in SPEC["workloads"]:
        assert NAME.match(cell["name"]) and cell["name"] not in names
        names.add(cell["name"])
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        c, config, traffic = common.load_cell(cell["name"])
        assert config["name"] == cell["config"]
        assert harness.driver(config["kind"]).run
        reports = {m["name"] for m in common.metrics_for(cell["name"],
                                                         False)}
        assert "setup_s" in reports and len(reports) >= 2
        assert common.metrics_for(cell["name"], True)
    for conf in SPEC["configs"]:
        path = ROOT / conf["file"]
        assert path.is_file() and str(conf["file"]).startswith("bench/")
        assert set(conf["reduced"]) <= set(json.loads(path.read_text()))
