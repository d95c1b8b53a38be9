"""BENCHMARK.json and the files it names: every cell, configuration, traffic
mix, limit and per-layer metric is found by its name, and the file keeps the
benchmark contract's shape."""

import json
import re

import pytest

from bench import generate, harness

BM = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024
    for p in BM["paths"]:
        assert (harness.ROOT / p).is_dir()


def test_names_units_and_keys():
    metrics = BM["end_to_end"] + BM["per_layer"]
    for group in (BM["configs"], BM["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in BM["end_to_end"]} >= {"setup_s"}
    lines = [x["why"] for x in BM["configs"] + BM["workloads"]] + [
        m["layer"] for m in BM["per_layer"]] + BM["command"]
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in lines)
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = harness.find_cell(name, BM)
    entry = next(w for w in BM["workloads"] if w["name"] == name)
    assert cell.config["name"] == entry["config"]
    assert cell.traffic["name"] == entry["traffic"]
    assert callable(getattr(generate, {
        "hot_spot": "hot_spot_counts", "hot_expert": "hot_expert_pairs",
        "lm_batches": "lm_batches"}[cell.traffic["pattern"]]))
    assert hasattr(cell.reference(), "__doc__")
    runner = harness.load_module(
        harness.BENCH / "runners" / f"{cell.config['runner']}.py")
    assert hasattr(runner.Runner, "check")
    assert cell.limits and all(v >= 0 for v in cell.limits.values())
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py").read)


def test_config_files_are_their_own():
    files = [c["file"] for c in BM["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_four_chip_cells_at_most_half():
    chips = [w["chips"] for w in BM["workloads"]]
    assert set(chips) <= {1, 4}
    assert sum(c == 4 for c in chips) <= max(1, len(chips) // 2)


def test_peaks_table():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")


def test_unknown_workload_refused():
    with pytest.raises(KeyError):
        harness.find_cell("no-such-cell", BM)
