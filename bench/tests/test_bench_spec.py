"""BENCHMARK.json keeps to the benchmark's contract: names and units of
the allowed characters, the keys each entry may have, every piece a cell
names present as a file, and a run length whose full check fits."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
# widths may never be cut: sizes, dims, ranks, expansions, experts per token
WIDTH = re.compile(r"(_size|_dim|_rank)$|expan|experts_per_tok")


def _text(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert all(_text(w) for w in SPEC["command"])
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_only_their_keys_and_clean_names(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = {"workloads"} if section in ("end_to_end", "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e and section != "end_to_end" and section != "per_layer":
                assert _text(e[k]), (k, e[k])
        if section == "per_layer":
            assert _text(e["layer"])


def test_configs_files_and_reductions():
    for c in SPEC["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith("bench/")
        conf = json.loads(path.read_text())
        assert conf["reduced"] == c["reduced"]
        assert conf["source"].startswith(c["source"])
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
            assert k in conf["published"], k
            assert conf["published"][k] != conf.get(k)


def test_cells_name_real_pieces():
    configs = {c["name"] for c in SPEC["configs"]}
    seen = set()
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and _text(w["why"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == configs
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


def test_metrics_cover_every_cell():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in SPEC["end_to_end"]}
    assert e2e["setup_s"] == cells
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        wl = set(m.get("workloads", cells))
        assert wl <= e2e[m["moves"]], m["name"]
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in cells:
        assert any(c in wl for n, wl in e2e.items() if n != "setup_s")
        assert any(c in m.get("workloads", cells) for m in SPEC["per_layer"])
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_every_kernel_roofline_has_a_step_mfu_beside_it():
    for m in SPEC["per_layer"]:
        if "_roofline" in m["name"]:
            kernel = m["name"].split(".")[1].removesuffix("_roofline")
            assert (ROOT / "bench" / "roofline" / f"{kernel}.py").is_file()
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and set(o["workloads"]) >= set(m["workloads"])
                       for o in SPEC["per_layer"]), m["name"]


def test_run_length_fits_a_full_check_with_24_cells():
    r = SPEC["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 + 1200 <= 43200
