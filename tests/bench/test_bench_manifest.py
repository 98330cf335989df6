"""BENCHMARK.json and the files it names keep to the benchmark's rules.

Each rule is a function of a checkout's root, so that a test can hold a copy
with files added to the same rules (``test_bench_family.py``)."""
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
# the cuts of a layer that ``model-configs`` section 4 allows beside depth,
# each with its floor: the chip's share of a layer's experts (at least 8)
# and of the vocabulary (at least an eighth), in a stated deployment
EXPERT_COUNTS = ("num_local_experts", "n_routed_experts")
MIN_EXPERTS = 8
MIN_VOCAB_SHARE = 1 / 8
# depth keeps at least four layers after the leading dense ones
MIN_LAYERS_AFTER_DENSE = 4


def manifest(root) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def check_names_units_and_keys(root) -> None:
    m = manifest(root)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in m[k]}) == len(m[k])
    metrics = m["end_to_end"] + m["per_layer"]
    assert len({x["name"] for x in metrics}) == len(metrics)
    assert all(UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
               for x in metrics)
    assert {"setup_s"} <= {x["name"] for x in m["end_to_end"]}
    for x in m["end_to_end"]:
        assert 0.01 <= x["bound"] <= 0.25
        assert x["source"] in ("host_clock", "device_trace")
    assert 1 <= m["run_seconds"] <= 51
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 2)


def check_cells(root) -> None:
    bench = common.Bench(root)
    m = bench.manifest
    e2e = {x["name"] for x in m["end_to_end"]}
    for w in m["workloads"]:
        mix = bench.traffic(w["traffic"])
        bench.config(w["config"])
        bench.limits(w["name"])
        assert (bench.root / "bench" / "drivers" / f"{mix['driver']}.py"
                ).is_file()
        assert (bench.root / "bench" / "traffic" / f"{mix['kind']}.py"
                ).is_file()
        mine = bench.metrics_of(w["name"], "per_layer")
        assert mine, w["name"]
        for x in mine:
            assert x["moves"] in e2e
            assert hasattr(bench.reader(x["name"]), "read")
            assert x["moves"] in {y["name"] for y in bench.metrics_of(
                w["name"], "end_to_end")}
        assert len(bench.metrics_of(w["name"], "end_to_end")) >= 2


def check_cuts(mc: dict, reduced: list) -> None:
    """No width is cut.  The vocabulary and the experts of a layer may be
    cut to the chip's share of a stated deployment, each with its published
    size in ``source_values`` and no further than its floor; depth keeps its
    floor too."""
    published = mc["source_values"]
    assert not [k for k in reduced if WIDTH.search(k) and k != "vocab_size"]
    if "vocab_size" in reduced:
        assert mc["deployment"] and "vocab_size" in published
        whole = published["vocab_size"]
        assert whole * MIN_VOCAB_SHARE <= mc["vocab_size"] < whole
    for k in EXPERT_COUNTS:
        if k in reduced:
            assert mc["deployment"] and k in published
            assert MIN_EXPERTS <= mc[k] < published[k]
    if "num_hidden_layers" in reduced:
        assert "num_hidden_layers" in published
        dense = mc.get("first_k_dense_replace", 0)
        assert mc["num_hidden_layers"] < published["num_hidden_layers"]
        assert mc["num_hidden_layers"] - dense >= MIN_LAYERS_AFTER_DENSE


def check_configs(root) -> None:
    bench = common.Bench(root)
    for c in bench.manifest["configs"]:
        mc = json.loads((bench.root / c["file"]).read_text())
        assert mc["name"] == c["name"] and c["file"].startswith("bench/")
        assert mc["reduced"] == c["reduced"]
        assert set(mc["source_values"]) <= set(mc["reduced"])
        check_cuts(mc, c["reduced"])
        assert mc["assumed"] and mc["deployment"] and mc["source"]
        bench.reference(mc["reference"]).check_fixed(mc)


def test_names_units_and_keys():
    check_names_units_and_keys(ROOT)


def test_every_cell_finds_its_files_and_reports_what_its_metrics_move():
    check_cells(ROOT)


def test_configs_state_their_cuts_and_cut_no_width():
    check_configs(ROOT)
