"""BENCHMARK.json and the files it names keep to the benchmark's rules."""
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(size|_dim|_rank|_head|heads|per_tok|expand)$")
M = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_names_units_and_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in M[k]}) == len(M[k])
    metrics = M["end_to_end"] + M["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert {"setup_s"} <= {m["name"] for m in M["end_to_end"]}
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= M["run_seconds"] <= 51
    for w in M["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(
        1, len(M["workloads"]) // 2)


def test_every_cell_finds_its_files_and_reports_what_its_metrics_move():
    bench = common.Bench(ROOT)
    e2e = {m["name"] for m in M["end_to_end"]}
    for w in M["workloads"]:
        mix = bench.traffic(w["traffic"])
        bench.config(w["config"])
        bench.limits(w["name"])
        assert (ROOT / "bench" / "drivers" / f"{mix['driver']}.py").is_file()
        assert (ROOT / "bench" / "traffic" / f"{mix['kind']}.py").is_file()
        mine = bench.metrics_of(w["name"], "per_layer")
        assert mine, w["name"]
        for m in mine:
            assert m["moves"] in e2e
            assert hasattr(bench.reader(m["name"]), "read")
            assert m["moves"] in {x["name"] for x in bench.metrics_of(
                w["name"], "end_to_end")}
        assert len(bench.metrics_of(w["name"], "end_to_end")) >= 2


def test_configs_state_their_cuts_and_cut_no_width():
    bench = common.Bench(ROOT)
    for c in M["configs"]:
        mc = json.loads((ROOT / c["file"]).read_text())
        assert mc["name"] == c["name"] and c["file"].startswith("bench/")
        assert mc["reduced"] == c["reduced"]
        assert set(mc["source_values"]) <= set(mc["reduced"])
        assert not [k for k in c["reduced"] if WIDTH.search(k)]
        assert mc["assumed"] and mc["deployment"] and mc["source"]
        bench.reference(mc["reference"]).check_fixed(mc)
