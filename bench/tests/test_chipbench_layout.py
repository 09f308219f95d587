"""Every cell of BENCHMARK.json resolves its configuration, traffic mix,
driver and per-layer metric readers by name, and the file keeps to the
benchmark's schema.  Nothing here needs a chip or the program."""
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    SPEC = json.load(_fh)
CELLS = [w["name"] for w in SPEC["workloads"]]
PENDING = sorted(f[:-len(".json")]
                 for f in os.listdir(os.path.join(BENCH, "pending")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS + PENDING)
def test_cell_resolves_by_name(cell):
    res = harness.resolve(harness.with_pending(SPEC, cell), cell)
    conf, traffic = res["config"], res["traffic"]
    assert conf["name"] == res["cell"]["config"]
    driver = harness.load_module("drivers", traffic["driver"])
    for fn in ("setup", "window", "release", "check"):
        assert callable(getattr(driver, fn)), (cell, fn)
    names = {m["name"] for m in res["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert res["per_layer"], cell
    for m in res["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    # Every limit a check holds is a number set in the traffic file.
    assert traffic["limits"], cell
    assert all(isinstance(v, (int, float)) for v in traffic["limits"].values())


def test_unknown_cell_and_missing_module_are_refused():
    with pytest.raises(harness.CellError):
        harness.resolve(SPEC, "no-such-cell")
    for cell in PENDING:        # run.py resolves from BENCHMARK.json alone
        assert cell not in CELLS
        with pytest.raises(harness.CellError):
            harness.resolve(SPEC, cell)
    with pytest.raises(harness.CellError):
        harness.load_module("metrics", "no.such_metric")


def test_schema():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"])) as fh:
            assert json.load(fh)["reduced"] == c["reduced"]
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    four = 0
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        four += w["chips"] == 4
    assert four <= max(1, len(CELLS) // 2)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert set(m["workloads"]) <= set(CELLS)
        for cell in m["workloads"]:
            listed = e2e[m["moves"]].get("workloads", CELLS)
            assert cell in listed, (m["name"], cell)
        layers.add(m["layer"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for name in CELLS + list(configs):
        assert NAME.match(name)
    assert len(json.dumps(SPEC)) < 64 * 1024
