"""`BENCHMARK.json` finds every file it names, and keeps to its form."""
import json
import re

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_name_resolves_to_its_files():
    b = harness.load_benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for w in b["workloads"]:
        cell, entry, traffic = harness.find_cell(b, w["name"])
        conf = harness.load_config(entry)
        assert conf["name"] == entry["name"]
        assert (harness.BENCH / f"{traffic['kind']}.py").exists()
        limits = harness.load_limits(w["name"])
        keys = {"train": {"loss_gap", "grad_gap", "change_gap"},
                "serve": {"served_gap"}}[traffic["kind"]]
        assert keys <= set(limits)
        assert w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        # every cell reports setup_s, another end-to-end metric, and a
        # per-layer metric
        e2e = harness.metrics_for(b, w["name"], trace=False)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert harness.metrics_for(b, w["name"], trace=True)
    for m in b["per_layer"]:
        assert harness.metric_reader(m["name"])
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}


def test_names_units_and_bounds():
    b = harness.load_benchmark()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert len(json.dumps(b)) < 64 * 1024
