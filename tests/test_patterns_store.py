"""PatternStore: the flock-journaled, multi-process Performance Pattern
Inheritance store (paper §3.2).

Covers the journal mechanics the executor-conformance suite builds on:
merge-on-replay, tail visibility across store instances, compaction,
corrupt-line quarantine (the truncated-store crash bugfix), legacy
whole-file-array migration, the wire form, and the N-process hammer
race (mirroring ``tests/_evalcache_proc.py``)."""
import json
import os
import subprocess
import sys

import pytest

from repro.core import (EvalCache, EvalRecord, PatternStore,
                        canonical_spec, get_case)

HELPER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_patterns_proc.py")


def _case():
    return get_case("gemm")


def _base():
    return dict(_case().baseline_variant)


def _lines(path):
    with open(path) as f:
        return [line for line in f if line.strip()]


# ------------------------------------------------------- merge + replay ---
def test_merge_keeps_best_gain_and_journal_replays(tmp_path):
    path = str(tmp_path / "pat.jsonl")
    s = PatternStore(path)
    base = _base()
    s.record(_case(), "cpu", base, dict(base, block_m=128), 2.0)
    s.record(_case(), "cpu", base, dict(base, block_m=128), 3.0)  # better
    s.record(_case(), "cpu", base, dict(base, block_m=128), 2.5)  # worse
    assert len(s) == 1 and s.patterns[0].gain == 3.0
    # a fresh store replays the journal to the same merged state
    s2 = PatternStore(path)
    assert len(s2) == 1 and s2.patterns[0].gain == 3.0
    assert s2.quarantined == 0


def test_below_threshold_empty_or_nonfinite_not_recorded(tmp_path):
    s = PatternStore(str(tmp_path / "pat.jsonl"))
    base = _base()
    assert s.record(_case(), "cpu", base, dict(base), 5.0) is None
    assert s.record(_case(), "cpu", base, dict(base, block_m=128),
                    1.01) is None
    # a non-finite gain (zero/failed timing) would journal as null and
    # be quarantined on every replay — must be rejected up front
    assert s.record(_case(), "cpu", base, dict(base, block_m=128),
                    float("inf")) is None
    assert s.record(_case(), "cpu", base, dict(base, block_m=128),
                    float("nan")) is None
    assert len(s) == 0 and not os.path.exists(s.path)


def test_tail_reload_makes_other_instances_wins_visible(tmp_path):
    """Two stores on one file stand in for two worker processes: a win
    recorded through one is suggested through the other without any
    explicit refresh call (suggest tail-reloads)."""
    path = str(tmp_path / "pat.jsonl")
    a, b = PatternStore(path), PatternStore(path)
    base = _base()
    a.record(_case(), "cpu", base, dict(base, block_k=256), 4.0)
    hints = b.suggest(get_case("syrk"), "cpu")
    assert {"block_k": 256} in hints


def test_provenance_fields_stamped(tmp_path):
    s = PatternStore(str(tmp_path / "pat.jsonl"), namespace="hostX:t")
    base = _base()
    p = s.record(_case(), "cpu", base, dict(base, block_m=128), 2.0)
    assert p.ns == "hostX:t" and p.pid == os.getpid() and p.ts > 0
    line = json.loads(open(s.path).read().splitlines()[0])
    assert line["ns"] == "hostX:t" and line["pid"] == os.getpid()


# ------------------------------------------------------------ wire form ---
def test_spec_roundtrip_and_in_memory_rejection(tmp_path):
    s = PatternStore(str(tmp_path / "pat.jsonl"), namespace="nsA")
    spec = json.loads(json.dumps(s.to_spec()))
    back = PatternStore.from_spec(spec)
    assert back.path == s.path and back.namespace == "nsA"
    with pytest.raises(ValueError, match="file-backed"):
        PatternStore().to_spec()


# ----------------------------------------------------------- compaction ---
def test_compaction_bounds_journal_and_preserves_state(tmp_path):
    s = PatternStore(str(tmp_path / "pat.jsonl"))
    s.COMPACT_MIN_LINES = 8
    base = _base()
    for i in range(50):
        s.record(_case(), "cpu", base, dict(base, block_m=128),
                 1.5 + i * 0.1)
    with open(s.path) as f:
        n_lines = sum(1 for line in f if line.strip())
    assert n_lines <= s.COMPACT_MIN_LINES
    s2 = PatternStore(s.path)
    assert len(s2) == 1 and s2.patterns[0].gain == pytest.approx(6.4)


@pytest.mark.parametrize("kind", ["PatternStore", "EvalCache"])
def test_reader_survives_concurrent_compaction(kind, tmp_path):
    """A store whose file is compacted (inode swap) under it rebuilds
    its view from the new journal on the next read."""
    if kind == "EvalCache":
        path = str(tmp_path / "cache.jsonl")
        reader, writer = EvalCache(path), EvalCache(path)

        def spec(i):
            return canonical_spec("gemm", {"t": i}, 1, "cpu")

        writer.get_or_compute(spec(0), lambda: EvalRecord(time_s=1.0))
        reader.reload()
        assert reader.lookup(spec(0)).time_s == 1.0     # caught up
        writer.COMPACT_MIN_LINES = 4
        for n in range(20):       # churn one key: the writer compacts
            writer.get_or_compute(
                spec(1), lambda n=n: EvalRecord(time_s=2.0 + n),
                accept=lambda r: False)
        writer.get_or_compute(spec(2), lambda: EvalRecord(time_s=9.0))
        assert len(_lines(path)) < 22                   # it did compact
        reader.reload()
        assert reader.lookup(spec(1)).time_s == 21.0    # last wins
        assert reader.lookup(spec(2)).time_s == 9.0
        assert len(reader) == len(writer) == 3
        return
    path = str(tmp_path / "pat.jsonl")
    reader, writer = PatternStore(path), PatternStore(path)
    base = _base()
    writer.record(_case(), "cpu", base, dict(base, block_m=128), 2.0)
    assert len(reader.suggest(_case(), "cpu")) == 1     # reader caught up
    writer.COMPACT_MIN_LINES = 4
    for i in range(20):
        writer.record(_case(), "cpu", base, dict(base, block_n=64 + i), 2.0)
    writer.record(_case(), "cpu", base, dict(base, block_k=256), 9.0)
    hints = reader.suggest(get_case("syrk"), "cpu", max_hints=64)
    assert {"block_k": 256} in hints
    assert len(hints) == len(writer.patterns)


# ------------------------------------- corruption quarantine (bugfix) -----
def test_corrupt_journal_line_quarantined_with_warning(tmp_path):
    path = str(tmp_path / "pat.jsonl")
    s = PatternStore(path)
    base = _base()
    s.record(_case(), "cpu", base, dict(base, block_m=128), 2.0)
    with open(path, "ab") as f:      # torn write from a crashed process
        f.write(b'{"family": "matmul", "platfo\n')
    s.record(_case(), "cpu", base, dict(base, block_n=64), 3.0)
    with pytest.warns(RuntimeWarning, match="quarantined"):
        s2 = PatternStore(path)
    assert len(s2) == 2 and s2.quarantined == 1
    assert os.path.exists(path + ".quarantine")
    # quarantining compacts the bad line out of the journal, so the
    # store stays fully usable and later readers neither re-quarantine
    # nor re-warn (the quarantine side file keeps the one copy)
    s2.record(_case(), "cpu", base, dict(base, block_k=256), 4.0)
    s3 = PatternStore(path)
    assert len(s3) == 3 and s3.quarantined == 0


def test_truncated_legacy_store_does_not_crash_init(tmp_path):
    """The original bug: a whole-file JSON array store truncated by a
    crash mid-``os.replace`` made ``PatternStore.__init__`` raise.  It
    must tolerate, quarantine, and carry on."""
    path = str(tmp_path / "pat.json")
    with open(path, "w") as f:
        f.write('[\n {"family": "matmul", "platform": "cp')   # torn
    with pytest.warns(RuntimeWarning, match="quarantined"):
        s = PatternStore(path)
    assert len(s) == 0 and s.quarantined == 1
    base = _base()
    s.record(_case(), "cpu", base, dict(base, block_m=128), 2.0)
    assert len(PatternStore(path)) == 1      # clean journal from here on


def test_legacy_array_store_migrates_to_journal(tmp_path):
    path = str(tmp_path / "pat.json")
    with open(path, "w") as f:
        json.dump([{"family": "matmul", "platform": "cpu",
                    "delta": {"block_m": 128}, "gain": 2.5,
                    "source_kernel": "gemm", "ts": 1.0}], f, indent=1)
    s = PatternStore(path)
    assert len(s) == 1 and s.patterns[0].gain == 2.5
    with open(path) as f:                    # rewritten as JSONL
        lines = [json.loads(line) for line in f if line.strip()]
    assert len(lines) == 1 and lines[0]["delta"] == {"block_m": 128}


# ------------------------------------------------ multi-process hammer ----
@pytest.mark.slow
def test_multiprocess_hammer_no_lost_or_torn_patterns(tmp_path):
    """N processes hammer one store file — distinct patterns, a shared
    contended delta, and forced compactions racing the appends.  No
    pattern may be lost, no journal line corrupted (mirrors the
    ``_evalcache_proc`` race tests)."""
    path = str(tmp_path / "pat.jsonl")
    writers, n = 4, 50
    procs = [subprocess.Popen([sys.executable, HELPER, "hammer",
                               path, str(w), str(n)])
             for w in range(writers)]
    for p in procs:
        assert p.wait(timeout=120) == 0
    with open(path) as f:                    # every line is whole JSON
        for line in f:
            if line.strip():
                json.loads(line)
    store = PatternStore(path)
    assert store.quarantined == 0
    merged = {json.dumps(p.delta, sort_keys=True): p
              for p in store.patterns}
    for w in range(writers):
        for i in range(n):
            key = json.dumps({"writer": w, "i": i}, sort_keys=True)
            assert key in merged, f"lost pattern writer={w} i={i}"
    shared = merged[json.dumps({"block_m": 128}, sort_keys=True)]
    # the globally best observation of the contended delta won the merge
    assert shared.gain == pytest.approx(1.5 + (writers - 1)
                                        + (n - 1) * 0.001)
