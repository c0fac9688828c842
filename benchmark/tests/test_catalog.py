"""Every cell's configuration, traffic mix, per-layer metrics and plain
reference load by the names in BENCHMARK.json."""

import re

import pytest

import run

BENCH = run.load_json("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    r = run.resolve(BENCH, cell)
    assert r["config"]["name"] == r["cell"]["config"]
    assert r["traffic"]["name"] == r["cell"]["traffic"]
    assert set(r["reference"].LIMITS) and hasattr(r["reference"], "check")
    assert r["readers"], "a cell reports at least one per-layer metric"
    for name, mod in r["readers"].items():
        assert mod.read({}) is None, f"{name} reads something from nothing"
    assert {m["name"] for m in r["end_to_end"]} >= {"setup_s"}
    chunk = run.ns(r["traffic"]["chunk_sim_s"])
    span = r["traffic"]["pass_sim_s"]
    assert chunk > 0 and (not span or run.ns(span) % chunk == 0)


def test_names_and_keys():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    for c in BENCH["configs"]:
        cfg = run.load_json(c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert len(c["source"]) <= 200


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        run.resolve(BENCH, "no-such-cell")


def test_peaks_name_their_source_and_refuse_unknown_kinds():
    peaks = run.load_json("benchmark/peaks.json")
    assert peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.NoChip):
        run.find_chips(1, peaks)   # the CPU is no chip
