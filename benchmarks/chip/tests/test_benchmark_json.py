"""``BENCHMARK.json`` is driven by data: every name it gives resolves to a
file of its own under the benchmark's directory, and its entries keep the
shape and limits the check expects."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
CHIP = ROOT / "benchmarks" / "chip"
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["benchmarks/chip"]
    assert B["command"][1].startswith("benchmarks/chip/")
    assert 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) < 64 * 1024


def test_configs_and_cells_resolve():
    used = {w["config"] for w in B["workloads"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert (CHIP / "references" / f"{conf['reference']}.py").exists()
        assert (CHIP / "builders" / f"{conf['builder']}.py").exists()
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size")), k
    names = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["name"] not in names
        names.add(w["name"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        tr = json.loads((CHIP / "traffic" / f"{w['traffic']}.json")
                        .read_text())
        if tr["mode"] == "serve":
            kind = tr["arrivals"]["kind"]
            assert (CHIP / "arrivals" / f"{kind}.py").exists()
        assert (CHIP / f"{tr['mode']}_loop.py").exists()


def test_metrics_resolve_and_cover_cells():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    seen = set()
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        if m["name"] != "setup_s":
            assert (CHIP / "metrics" / f"{m['name']}.py").exists(), m
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        reports = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(reports), m["name"]
    for cell in cells:
        mine = [m for m in B["end_to_end"]
                if cell in m.get("workloads", [cell])]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", [cell])
                   for m in B["per_layer"])


@pytest.mark.parametrize("m", [m for m in B["per_layer"]
                               if m["unit"] == "%"],
                         ids=lambda m: m["name"])
def test_roofline_and_mfu_names(m):
    if "roofline" in m["name"]:
        assert m["name"].split(".")[0].endswith("_roofline")
    if m["layer"] == "kernels":
        mfu = [x for x in B["per_layer"] if "mfu" in x["name"]
               and x["moves"] == m["moves"]]
        assert mfu, f"{m['name']} has no whole-step mfu beside it"
