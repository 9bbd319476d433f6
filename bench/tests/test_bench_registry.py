"""A configuration, a traffic mix, a cell and a per-layer metric added as
new files with new entries are found by name; no existing file changes."""
import hashlib
import json
import os

from bench import spec
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
