"""Harness coverage for benchmarks/collect.py and benchmarks/run.py.

The bench driver is CI's gatekeeper (a red bench must exit nonzero) and
the profile cache is the corpus every MRE bench reads — neither had a
test before this file.
"""

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import collect, run as bench_run
from repro.core.features import ProfileRecord, record_to_json


def _record(name="m0"):
    return ProfileRecord(
        model_name=name, family="dense", batch_size=4, input_size=32,
        channels=64, learning_rate=1e-3, epoch=1, optimizer="adamw",
        layers=3, flops=1e9, params=1000,
        nsm_edges={("dot", "add"): 4.0}, time_s=0.5, mem_bytes=2e6)


# -- collect.py ---------------------------------------------------------------


def test_load_cache_skips_corrupt_lines(tmp_path, monkeypatch):
    cache = tmp_path / "profiles.jsonl"
    combo = {"kind": "zoo", "name": "lenet5", "batch": 8, "image": 32}
    good = {"key": collect._key(combo), "record": record_to_json(_record())}
    cache.write_text(json.dumps(good) + "\n"
                     "{not json at all\n"
                     '{"key_is_missing": 1}\n')
    monkeypatch.setattr(collect, "CACHE", str(cache))
    loaded = collect._load_cache()
    assert list(loaded) == [collect._key(combo)]


def test_collect_serves_cached_records_without_profiling(tmp_path,
                                                         monkeypatch):
    cache = tmp_path / "profiles.jsonl"
    combo = {"kind": "zoo", "name": "lenet5", "batch": 8, "image": 32}
    cache.write_text(json.dumps(
        {"key": collect._key(combo),
         "record": record_to_json(_record("lenet5"))}) + "\n")
    monkeypatch.setattr(collect, "CACHE", str(cache))
    # any cache miss would profile for real — fail the test instead
    monkeypatch.setattr(collect, "_profile",
                        lambda c: pytest.fail("cache should have hit"))
    recs = collect.collect([combo], verbose=False)
    assert len(recs) == 1
    assert recs[0].model_name == "lenet5"
    assert recs[0].time_s == 0.5


def test_collect_appends_new_records_to_cache(tmp_path, monkeypatch):
    cache = tmp_path / "profiles.jsonl"
    combo = {"kind": "zoo", "name": "nin", "batch": 8, "image": 32}
    monkeypatch.setattr(collect, "CACHE", str(cache))
    monkeypatch.setattr(collect, "_profile", lambda c: _record("nin"))
    recs = collect.collect([combo], verbose=False)
    assert len(recs) == 1 and cache.exists()
    # second call round-trips through the freshly written cache
    monkeypatch.setattr(collect, "_profile",
                        lambda c: pytest.fail("cache should have hit"))
    again = collect.collect([combo], verbose=False)
    assert again[0].model_name == "nin"


def test_collect_keys_records_by_device(tmp_path, monkeypatch):
    """A record measured on another device is never served as this one's."""
    cache = tmp_path / "profiles.jsonl"
    combo = {"kind": "zoo", "name": "nin", "batch": 8, "image": 32}
    other = dict(json.loads(collect._key(combo)), platform="tpu",
                 device_kind="TPU v5 lite")
    cache.write_text(json.dumps({"key": json.dumps(other, sort_keys=True),
                                 "record": record_to_json(_record("nin"))})
                     + "\n")
    monkeypatch.setattr(collect, "CACHE", str(cache))
    assert collect.all_cached() == []
    monkeypatch.setattr(collect, "_profile", lambda c: _record("fresh"))
    recs = collect.collect([combo], verbose=False)
    assert recs[0].model_name == "fresh"  # profiled here, not the tpu label
    assert [r.model_name for r in collect.all_cached()] == ["fresh"]


def test_collect_keys_records_by_attention_path(tmp_path, monkeypatch):
    """A label profiled through the flash kernel is never served for a step
    that takes the materialised XLA path, nor the other way round."""
    cache = tmp_path / "profiles.jsonl"
    combo = {"kind": "lm", "name": "qwen2-0.5b", "batch": 2, "seq": 64}
    mine = json.loads(collect._key(combo))
    assert mine["attention"] == "xla"  # the CPU takes XLA
    other = dict(mine, attention="flash>=1024")
    cache.write_text(json.dumps({"key": json.dumps(other, sort_keys=True),
                                 "record": record_to_json(_record("flash"))})
                     + "\n")
    monkeypatch.setattr(collect, "CACHE", str(cache))
    assert collect.all_cached() == []
    monkeypatch.setattr(collect, "_profile", lambda c: _record("fresh"))
    assert collect.collect([combo], verbose=False)[0].model_name == "fresh"


def test_collect_fails_on_a_failed_profile(tmp_path, monkeypatch):
    monkeypatch.setattr(collect, "CACHE", str(tmp_path / "profiles.jsonl"))

    def boom(combo):
        raise RuntimeError("compile refused")

    monkeypatch.setattr(collect, "_profile", boom)
    with pytest.raises(RuntimeError, match="compile refused") as info:
        collect.collect([{"kind": "zoo", "name": "nin"}], verbose=False)
    assert any("nin" in note for note in info.value.__notes__)


# -- run.py -------------------------------------------------------------------


def _fake_bench(monkeypatch, name, run_fn):
    mod = types.ModuleType(f"benchmarks._fake_{name}")
    mod.run = run_fn
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return (name, mod.__name__)


def test_run_exits_nonzero_when_a_bench_raises(monkeypatch, capsys):
    benches = [
        _fake_bench(monkeypatch, "ok", lambda: [("metric", 1.0)]),
        _fake_bench(monkeypatch, "boom",
                    lambda: (_ for _ in ()).throw(RuntimeError("gate"))),
    ]
    monkeypatch.setattr(bench_run, "BENCHES", benches)
    assert bench_run.main([]) == 1
    out = capsys.readouterr().out
    assert "ok.metric,1" in out
    assert "boom.wall_s" in out  # wall time still reported for the failure


def test_run_exits_zero_when_all_benches_pass(monkeypatch, capsys):
    benches = [_fake_bench(monkeypatch, "ok", lambda: [("metric", 2.0)])]
    monkeypatch.setattr(bench_run, "BENCHES", benches)
    assert bench_run.main([]) == 0
    assert "ok.metric,2" in capsys.readouterr().out


def test_run_only_filter(monkeypatch, capsys):
    ran = []
    benches = [
        _fake_bench(monkeypatch, "a", lambda: ran.append("a") or []),
        _fake_bench(monkeypatch, "b", lambda: ran.append("b") or []),
    ]
    monkeypatch.setattr(bench_run, "BENCHES", benches)
    assert bench_run.main(["--only", "b"]) == 0
    assert ran == ["b"]


def test_scenarios_bench_is_registered():
    assert ("scenarios", "benchmarks.bench_scenarios") in bench_run.BENCHES


def test_aggregate_artifacts(tmp_path):
    (tmp_path / "BENCH_refit.json").write_text(
        json.dumps({"time_mre_improvement": 3.0, "smoke": True}))
    (tmp_path / "BENCH_rpc.json").write_text(
        json.dumps({"resolve_errors": 0.0}))
    (tmp_path / "BENCH_broken.json").write_text("{truncated")
    (tmp_path / "BENCH_all.json").write_text(
        json.dumps({"stale": "previous aggregate"}))
    agg = bench_run.aggregate_artifacts(str(tmp_path))
    assert sorted(agg) == ["refit", "rpc"]  # corrupt + old aggregate skipped
    assert agg["refit"]["time_mre_improvement"] == 3.0


def test_run_aggregate_flag_writes_bench_all(monkeypatch, tmp_path):
    monkeypatch.chdir(tmp_path)
    (tmp_path / "BENCH_x.json").write_text(json.dumps({"v": 1.0}))
    monkeypatch.setattr(bench_run, "BENCHES", [])
    assert bench_run.main(["--aggregate"]) == 0
    agg = json.loads((tmp_path / "BENCH_all.json").read_text())
    assert agg == {"x": {"v": 1.0}}
