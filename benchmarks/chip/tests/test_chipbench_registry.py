"""Discovery by name, additions as new files, and BENCHMARK.json's shape."""
from __future__ import annotations

import json
import re

import chipbench_fixtures as fx  # noqa: F401  (puts the checkout on sys.path)

from benchmarks.chip.registry import HERE, ROOT, Registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_finds_the_benchmarks_own_files_by_name():
    reg = Registry()
    bench = reg.benchmark()
    for cell in bench["workloads"]:
        assert reg.config(cell["config"])["name"] == cell["config"]
        assert reg.traffic(cell["traffic"])["kind"] in ("train", "serve")
        assert reg.limits(cell["name"])
    for m in bench["per_layer"]:
        assert callable(reg.metric(m["name"]).read)


def test_a_later_cell_adds_files_and_edits_none(tmp_path):
    before = {p: p.read_bytes() for p in HERE.rglob("*") if p.is_file()
              and "__pycache__" not in p.parts}
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "newmodel.json").write_text(
        json.dumps({**fx.TINY, "name": "newmodel"}))
    (tmp_path / "traffic" / "serve.new.json").write_text(
        json.dumps({"kind": "serve", "rate_rps": 3.0}))
    (tmp_path / "metrics" / "new_metric.py").write_text(
        "KERNELS = ('my_kernel',)\n\ndef read(run):\n"
        "    return run['record'].get('x')\n")
    reg = Registry(roots=[tmp_path, HERE])
    assert reg.config("newmodel")["name"] == "newmodel"
    assert reg.traffic("serve.new")["rate_rps"] == 3.0
    mod = reg.metric("new_metric")
    assert mod.KERNELS == ("my_kernel",)
    assert mod.read({"record": {"x": 2.5}}) == 2.5
    assert mod.read({"record": {}}) is None
    # the benchmark's own files are still found, and none was changed
    assert reg.config("paper_150m")["hidden_size"] == 768
    after = {p: p.read_bytes() for p in HERE.rglob("*") if p.is_file()
             and "__pycache__" not in p.parts}
    assert after == before


def test_benchmark_json_keeps_the_contract():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"][1] == "benchmarks/chip/run.py"
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    reg = Registry()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        reported = [m["name"] for m in reg.metrics_of(w["name"],
                                                      "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2
        assert reg.metrics_of(w["name"], "per_layer")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        for cell in m.get("workloads", []):
            assert cell in cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
