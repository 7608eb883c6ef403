"""CPU tests of the span and scope reduction (``xscope.py``).

Run from the checkout root: ``python3 -m pytest chipbench/tests``.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import xscope, xtrace  # noqa: E402

TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"


def _planes():
    """One window of two steps: program spans on the window's thread and
    on a producer thread, device ops in five scopes and none."""
    main = [["window", 0, 200],
            ["train", 0, 100], ["data", 1, 10], ["loader/wait", 2, 8],
            ["step", 12, 84], ["step/dispatch", 12, 8], ["dispatch", 13, 6],
            ["step/block", 80, 15],
            ["train", 100, 100], ["loader/wait", 100, 30], ["step", 130.5, 66.5],
            ["step/retry", 130.5, 66], ["step/dispatch", 131, 9],
            ["step/block", 140, 56]]
    producer = [["loader/produce", 70, 6], ["loader/produce", 100, 90]]
    ops = [("%while.1 = (s32[]) while(%t)", 30, 40, "other"),
           ("%fusion.1 = bf16[8]{0} fusion(%a)", 35, 20, "mlp"),
           ("%fusion.2 = bf16[8]{0} fusion(%b)", 55, 10, "attention"),
           ("%fusion.3 = f32[8]{0} fusion(%c)", 75, 15, "norm"),
           ("%fusion.4 = f32[8]{0} fusion(%d)", 150, 30, "lm_head"),
           ("%fusion.5 = f32[8]{0} fusion(%e)", 180, 10, "optimizer"),
           ("%fusion.6 = f32[8]{0} fusion(%f)", 250, 10, "embed")]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": main},
                                        {"name": "python3", "events": producer}]},
        {"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops", "events": [[n, s, d] for n, s, d, _ in ops],
            "scopes": [sc for *_, sc in ops]}]},
    ]


def test_scope_time_is_self_time_by_scope():
    r = xscope.reduce(_planes(), long_s=50e-9)
    # the loop's 40 less its body's 30 is the loop's own, with no scope;
    # the embed op lies outside the window
    assert r["scopes"] == pytest.approx({
        "other": 10e-9, "mlp": 20e-9, "attention": 10e-9, "norm": 15e-9,
        "lm_head": 30e-9, "optimizer": 10e-9})
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])


def test_gaps_take_the_window_threads_innermost_span():
    r = xscope.reduce(_planes(), long_s=50e-9)
    # [0, 30): inside the benchmark's dispatch, but the program's span wins;
    # [70, 75): the producer's span is not the window thread's, so the
    # benchmark's step; [90, 150): waiting on the loader; [190, 200): in
    # the block of a retried attempt
    assert r["idle_by_span"] == pytest.approx({
        "step/dispatch": 30e-9, "step": 5e-9, "loader/wait": 60e-9,
        "step/block": 10e-9})
    # cut at the spans' edges, each piece put down to its innermost span:
    # [0, 30) is train, data, loader/wait, data, train, step/dispatch (over
    # the benchmark's dispatch), step; [90, 150) is step/block, step,
    # train, loader/wait, train, step/retry, step/dispatch, step/block
    assert r["idle_split"] == pytest.approx({
        "train": (2 + 4 + 0.5 + 3) * 1e-9, "data": 2e-9,
        "loader/wait": (8 + 30) * 1e-9, "step/dispatch": (8 + 9) * 1e-9,
        "step": (10 + 5 + 1 + 0.5) * 1e-9, "step/block": (5 + 10 + 6) * 1e-9,
        "step/retry": 1e-9})
    assert sum(r["idle_split"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert r["long_gaps"] == [
        {"span": "loader/wait", "s": pytest.approx(60e-9), "step": 1, "retries": 1}]


def test_spans_count_every_thread_inside_the_window():
    r = xscope.reduce(_planes())
    assert {k: v["count"] for k, v in r["spans"].items()} == {
        "loader/wait": 2, "step/dispatch": 2, "step/block": 2, "step/retry": 1,
        "loader/produce": 2}
    assert r["spans"]["loader/wait"]["s"] == pytest.approx(38e-9)
    assert r["spans"]["loader/produce"]["s"] == pytest.approx(96e-9)
    ms = xscope.per_step_ms(r, 2)
    assert ms["loader_wait_ms.train"] == pytest.approx(19e-6)
    assert ms["dispatch_ms.train"] == pytest.approx(8.5e-6)
    assert ms["mlp_ms.train"] == pytest.approx(10e-6)
    assert "embed_ms.train" not in ms  # no embed op ran in the window


def test_the_benchmarks_numbers_are_xtraces():
    planes = _planes()
    r = xscope.reduce(planes)
    base = xtrace.reduce(xscope._benchmark_view(planes))
    for key in ("busy_s", "window_s", "devices", "device_ops"):
        assert r[key] == base[key], key


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/mlp/...d,dgf->...gf/dot_general", "mlp"),
    ("jit(train_step)/transpose(jvp(lm_head))/add_any", "lm_head"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/norm/rsqrt", "norm"),
    ("jit(train_step)/optimizer/jit(clip)", "optimizer"),
    ("jit(train_step)/jvp()/while/body/closed_call/attention/jit(_where)/select_n", "attention"),
    ("jit(train_step)/jvp()/while/body/dynamic_slice", "other"),
    ("", "other"),
])
def test_scope_of_takes_the_innermost_known_name(op_name, scope):
    assert xscope.scope_of(op_name) == scope


def test_op_names_read_compiled_hlo():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("norm"):
            return (y / jnp.sum(y)).sum()

    text = jax.jit(jax.grad(f)).lower(jnp.ones((8, 8))).compile().as_text()
    names = xscope.op_names(text)
    assert names
    assert {xscope.scope_of(n) for n in names.values()} >= {"mlp", "norm"}
    line = next(ln for ln in text.splitlines() if "op_name=" in ln and " = " in ln)
    inst = line.split(" = ")[0].split()[-1].lstrip("%")
    assert inst in names


def test_a_trace_recorded_on_the_chip_keeps_its_numbers():
    """The trace recorded before the program had spans or scopes reduces
    to the numbers ``xtrace`` gave it."""
    planes = json.loads(gzip.decompress(
        (TESTDATA / "qwen2_train_trace.json.gz").read_bytes()))
    r = xscope.reduce(planes)
    assert r["busy_s"] == pytest.approx(0.337571608, rel=1e-12)
    assert r["window_s"] == pytest.approx(0.352292309, rel=1e-12)
    assert r["device_ops"] == [[n, pytest.approx(t, rel=1e-12)] for n, t in [
        ["fusion.530 bf16[8,1024,2,4864]", 0.020365244],
        ["fusion.494 bf16[8,1024,2,4864]", 0.019098024],
        ["fusion.496 f32[896]", 0.019054599],
        ["fusion.505 bf16[1,896,2,4864]", 0.018843566],
        ["multiply_reduce_fusion.5 bf16[8,14,1024]", 0.017405726],
        ["fusion.500 bf16[8,14,1024,64]", 0.017173588],
        ["convert_bitcast_fusion.2 f32[8,1024,14,64]", 0.01661966],
        ["fusion.227 f32[896]", 0.013659321],
        ["fusion.207 bf16[152064,896]", 0.013224701],
        ["fusion.181 f32[8,1024]", 0.012272362]]]
    assert r["scopes"] == pytest.approx({"other": r["busy_s"]})
    assert r["spans"] == {}


def test_a_scoped_trace_recorded_on_the_chip():
    """Two steps of ``qwen2-0.5b.train_b8s1024`` on a TPU v5e, scopes
    joined from the compiled step's HLO by ``load``, op names shortened
    to ``xtrace.op_name``'s form."""
    planes = json.loads(gzip.decompress(
        (TESTDATA / "qwen2_train_scoped_trace.json.gz").read_bytes()))
    r = xscope.reduce(planes)
    assert set(r["scopes"]) == {"embed", "attention", "mlp", "norm", "lm_head",
                                "optimizer", "other"}
    assert r["scopes"]["other"] < 0.1 * r["busy_s"]
    assert sum(r["scopes"].values()) == pytest.approx(r["busy_s"])
    assert {k: v["count"] for k, v in r["spans"].items()} == {
        "loader/wait": 2, "loader/produce": 2, "step/dispatch": 2, "step/block": 2}
    # the host was in a program span on the window's thread at every gap
    assert {n for n, _ in r["idle_gaps"]} <= {"step/dispatch", "step/block"}
    ms = xscope.per_step_ms(r, 2)
    assert set(ms) == {f"{sc}_ms.train" for sc in xscope.STEP_SCOPES} | set(xscope.STEP_SPANS)
