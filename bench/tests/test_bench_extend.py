"""A configuration, a traffic mix and a per-layer metric are added by new
files and new ``BENCHMARK.json`` entries alone: no file is edited."""
import hashlib
import json
import os
import re
import shutil

import pytest

from bench import harness

BENCH = harness.BENCH
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def spec():
    return harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_names_and_units_use_allowed_characters():
    s = spec()
    names = [c["name"] for c in s["configs"]]
    names += [w["name"] for w in s["workloads"]]
    names += [w["config"] for w in s["workloads"]]
    names += [w["traffic"] for w in s["workloads"]]
    names += [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    names += [k for c in s["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    units = [m["unit"] for m in s["end_to_end"] + s["per_layer"]]
    assert all(UNIT.match(u) for u in units), units


def test_every_entry_resolves_to_its_files():
    s = spec()
    for w in s["workloads"]:
        cell, cfg, tr = harness.resolve_cell(s, w["name"])
        assert os.path.exists(os.path.join(BENCH, "entries",
                                           tr["entry"] + ".py"))
        assert os.path.exists(os.path.join(BENCH, "recipes",
                                           cfg["recipe"] + ".py"))
    for c in s["configs"]:
        assert harness.load_json(os.path.join(harness.ROOT, c["file"]))[
            "name"] == c["name"]
    for m in s["per_layer"]:
        mod = harness.load_module(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))
        assert callable(mod.read)


def test_new_files_and_entries_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    before = digest(root / "bench")
    b = root / "bench"

    cfg = json.loads((b / "configs" / "paper_spd16k.json").read_text())
    cfg.update(name="tiny_spd", n=256, k=2)
    (b / "configs" / "tiny_spd.json").write_text(json.dumps(cfg))
    tr = json.loads((b / "traffic" / "solve.json").read_text())
    tr.update(rhs_pool=1)
    (b / "traffic" / "solve_one_rhs.json").write_text(json.dumps(tr))
    (b / "metrics" / "engine.solves.py").write_text(
        "def read(r):\n    return r.counters.get('solves')\n")

    s = json.loads((root / "BENCHMARK.json").read_text())
    s["configs"].append({"name": "tiny_spd", "source": "x",
                         "file": "bench/configs/tiny_spd.json",
                         "reduced": ["n"], "why": "test"})
    s["workloads"].append({"name": "tiny.one_rhs", "config": "tiny_spd",
                           "traffic": "solve_one_rhs", "chips": 1,
                           "why": "test"})
    s["end_to_end"].append({"name": "tiny.sweep_ms", "unit": "ms",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["tiny.one_rhs"]})
    s["per_layer"].append({"name": "engine.solves", "unit": "solves",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine", "moves": "tiny.sweep_ms",
                           "workloads": ["tiny.one_rhs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(s))

    after = digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before

    cell, c, t = harness.resolve_cell(s, "tiny.one_rhs", bench_dir=str(b))
    assert c["n"] == 256 and t["rhs_pool"] == 1 and t["entry"] == "solve"
    e2e = [m["name"] for m in harness.cell_metrics(
        s, "tiny.one_rhs", "end_to_end")]
    assert {"setup_s", "tiny.sweep_ms"} <= set(e2e)
    assert "solve_s" not in e2e
    layers = harness.cell_metrics(s, "tiny.one_rhs", "per_layer")
    assert [m["name"] for m in layers] == ["engine.solves"]
    got = harness.read_per_layer(
        s, "tiny.one_rhs",
        harness.Readings({"solves": 7}, None, c, None, 1.0),
        bench_dir=str(b))
    assert got == {"engine.solves": {"value": 7.0, "unit": "solves"}}


def test_a_reader_with_nothing_to_read_is_left_out():
    s = spec()
    got = harness.read_per_layer(
        s, "spd16k.solve", harness.Readings({}, None, {}, None, 1.0))
    assert got == {}


@pytest.mark.parametrize("cell", [w["name"] for w in spec()["workloads"]])
def test_cells_name_existing_metrics(cell):
    s = spec()
    e2e = {m["name"] for m in harness.cell_metrics(s, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in harness.cell_metrics(s, cell, "per_layer"):
        assert m["moves"] in e2e


@pytest.mark.parametrize("name,want", [("solve_s", 2.0),
                                       ("solve_s.hpcg", 2.0),
                                       ("rate", 5.0)])
def test_qualified_metric_reads_its_quantity(name, want):
    assert harness.e2e_value({"solve_s": 2.0, "rate": 5.0}, name) == want
