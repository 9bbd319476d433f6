"""A configuration, a traffic mix, a cell, a per-layer metric and a family
added as new files with new entries are found by name; no existing file
changes.  Spec blocks and dotted keymap fields of a configuration reach
the program and are checked."""
import hashlib
import json
import os

import pytest

from bench import harness, spec, weights
from bench.tests import tiny


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    bench = tiny.make(str(tmp_path))
    before = _digests(bench.dir)
    # a new per-layer metric: one new reader file and one new entry
    with open(os.path.join(bench.dir, "metrics", "steps_in_window.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return run.window_steps[1] - run.window_steps[0]\n")
    bench.spec["per_layer"].append({
        "name": "steps_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "scheduler",
        "moves": "itl_p90_ms"})
    after = _digests(bench.dir)
    assert {k: v for k, v in after.items() if k in before} == before

    cell = bench.cell(tiny.WORKLOAD)
    assert cell.config["model"] == tiny.TINY_MODEL
    assert cell.traffic == tiny.TINY_TRAFFIC
    assert cell.settings == tiny.TINY_CELL
    names = [m["name"] for m in cell.per_layer]
    assert "steps_in_window" in names and "mfu.decode" in names
    rec = type("Run", (), {"window_steps": (3, 10)})()
    assert bench.metric_reader("steps_in_window")(rec) == 7
    assert bench.reference("dense").logits is not None


def test_every_cell_of_the_benchmark_loads():
    b = spec.Bench()
    assert len(b.spec["workloads"]) >= 1
    for w in b.spec["workloads"]:
        cell = b.cell(w["name"])
        assert {"slots", "max_len", "buckets", "rate_rps", "check"} <= \
            set(cell.settings)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(b.metric_reader(m["name"]))
        assert cell.traffic["prompt_len"]["max"] + \
            cell.traffic["max_new"]["max"] <= cell.settings["max_len"]
        assert cell.traffic["prompt_len"]["max"] <= \
            max(cell.settings["buckets"])


def test_benchmark_json_keys():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        d = json.load(f)
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["published"]) == set(c["reduced"])
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in d["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_a_new_family_is_new_files_only(tmp_path):
    """A family's module, configuration, mix and cell added to a copy of
    the tree change no file it had, and are found by name."""
    from repro.models import get_model
    bench = tiny.make(str(tmp_path))
    before = _digests(bench.dir)
    tiny.add_moe(bench)
    after = _digests(bench.dir)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        os.path.join("references", "tiny_moe.py"),
        os.path.join("configs", "tiny_moe.json"),
        os.path.join("traffic", "tiny_moe_chat.json"),
        os.path.join("cells", tiny.MOE_WORKLOAD + ".json")}

    cell = spec.Bench(root=bench.root, bench_dir=bench.dir).cell(
        tiny.MOE_WORKLOAD)
    assert cell.config["model"] == tiny.TINY_MOE_MODEL
    assert cell.family.__file__ == os.path.join(bench.dir, "references",
                                                "tiny_moe.py")
    harness.check_keymap(cell.config)
    m = cell.config["model"]
    program = get_model(harness.build_model_config(m, cell.config["arch"]))
    table = cell.family.shapes(m)
    weights.check_layout(weights.make(table, m, seed=1),
                         program.abstract_params())


def test_spec_blocks_reach_the_program():
    from repro.configs.base import MoESpec
    cfg = harness.build_model_config(tiny.TINY_MOE_MODEL, "qwen2-moe-a2.7b")
    assert cfg.moe == MoESpec(n_experts=8, top_k=2, d_ff_expert=32,
                              n_shared=1, d_ff_shared=64,
                              capacity_factor=4.0)
    assert cfg.n_layers == 2 and cfg.family == "moe"
    bad = dict(tiny.TINY_MOE_MODEL,
               moe=dict(tiny.TINY_MOE_MODEL["moe"], n_group=2))
    with pytest.raises(KeyError, match="n_group"):
        harness.build_model_config(bad, "qwen2-moe-a2.7b")
    with pytest.raises(KeyError, match="no moe spec"):
        harness.build_model_config(tiny.TINY_MOE_MODEL, "glm4-9b")
    with pytest.raises(KeyError, match="qk_scale"):
        harness.build_model_config(dict(tiny.TINY_MODEL, qk_scale=1.0),
                                   "glm4-9b")


def test_dotted_keymap_compares_a_nested_field():
    ok = tiny.TINY_MOE_CONFIG
    harness.check_keymap(ok)
    bad = dict(ok, config=dict(ok["config"], num_experts_per_tok=4))
    with pytest.raises(ValueError, match="moe.top_k"):
        harness.check_keymap(bad)
