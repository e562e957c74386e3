"""BENCHMARK.json keeps to its contract, and every name in it resolves to
the files that the harness looks up."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _names():
    out = [c["name"] for c in BENCH["configs"]]
    for w in BENCH["workloads"]:
        out += [w["name"], w["config"], w["traffic"]]
    out += [m["name"] for m in METRICS]
    for c in BENCH["configs"]:
        out += c["reduced"]
    return out


@pytest.mark.parametrize("name", _names())
def test_name_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")


def test_keys_and_uniqueness():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_command_stays_in_paths():
    cmd = BENCH["command"]
    assert (ROOT / cmd[1]).is_file()
    assert any(cmd[1].startswith(p + "/") for p in BENCH["paths"])
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    path = ROOT / config["file"]
    data = json.loads(path.read_text())
    assert data["name"] == config["name"]
    assert data["reduced"] == config["reduced"] == []
    assert 1 <= len(data["source"]) <= 200
    assert data["assumed"]
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(cell):
    """Configuration, traffic mix, traffic kind and metric readers of the
    cell all exist, and the cell reports setup_s, one other end-to-end
    metric and one per-layer metric."""
    assert (ROOT / "bench/configs" / f"{cell['config']}.json").is_file()
    mix = json.loads(
        (ROOT / "bench/traffic" / f"{cell['traffic']}.json").read_text())
    assert (ROOT / "bench/traffic" / f"{mix['kind']}.py").is_file()
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]

    def applies(m):
        return "workloads" not in m or cell["name"] in m["workloads"]
    e2e = {m["name"] for m in BENCH["end_to_end"] if applies(m)}
    assert "setup_s" in e2e and mix["metric"] in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if applies(m) and m["moves"] in e2e]
    assert layer
    for m in layer:
        assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()


def test_layers_are_consistent():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["layer"] and "\n" not in m["layer"]
        assert set(m["workloads"]) <= {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"]:
            assert m["unit"] == "%"
