"""BENCHMARK.json against the benchmark's contract, and discovery of
cells, configurations, traffic mixes and metric readers by name."""
import json
import re

import pytest

from chipbench import harness as H
from chipbench import traffic as T

BENCH = json.loads((H.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    cells = 24
    assert (2 + 14 * cells) * (BENCH["run_seconds"] + 60) \
        + cells * 180 + 1200 <= 43200


def test_names_units_and_entry_keys():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in seen
        seen.add(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in BENCH["end_to_end"]} <= {
        "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "output_tok_s",
        "setup_s"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


def test_every_cell_finds_its_files_by_name():
    for w in BENCH["workloads"]:
        cfg = H.load_config(w["config"])
        assert cfg["name"] == w["config"]
        entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
        assert (H.ROOT / entry["file"]).is_file()
        assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
        mix = T.load(w["traffic"])
        assert mix["rate_per_s"] > 0 and mix["warmup_s"] >= 0
        assert H.find_cell(BENCH, w["name"]) is w
    with pytest.raises(KeyError):
        H.find_cell(BENCH, "no.such.cell")
    with pytest.raises(FileNotFoundError):
        H.load_config("no-such-config")


def test_every_metric_has_a_reader_that_reads_nothing_from_nothing():
    cfg = H.load_config("minitron-8b")
    rec = H.Record(cfg=cfg, peak=H.peaks_for("TPU v5 lite"),
                   model=H.load_model(cfg))
    rec.counters = {"window": {
        "router": {"compression_attempts": 0, "compression_ms_sum": 0.0},
        "short": {"dispatches": 0, "decode_dispatches": 0}}}
    for m in BENCH["per_layer"]:
        assert H.load_reader(m["name"])(rec) is None
    with pytest.raises(FileNotFoundError):
        H.load_reader("no.such.metric")


def test_every_cell_reports_a_per_layer_and_two_end_to_end_metrics():
    for w in BENCH["workloads"]:
        assert H.metrics_for(BENCH, w["name"], "per_layer")
        names = {m["name"] for m in H.metrics_for(BENCH, w["name"],
                                                  "end_to_end")}
        assert "setup_s" in names and len(names) >= 2


def test_unknown_device_is_an_error():
    assert H.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(LookupError):
        H.peaks_for("cpu")


def test_the_command_refuses_the_cpu(capsys):
    from chipbench import run
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", BENCH["workloads"][0]["name"],
                  "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code != 0
    assert capsys.readouterr().out == ""
