"""CPU tests of the trace reduction's scopes and spans (``xtrace.py``)
and of the by-hand gap naming by the program's spans (``xscope.py``).

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
    body = "jit(train_step)/jvp()/while/body/closed_call/"
    ops = [("%while.1 = (s32[]) while(%t)", 30, 40, "jit(train_step)/jvp()/while"),
           ("%fusion.1 = bf16[8]{0} fusion(%a)", 35, 20, body + "mlp/dot_general"),
           ("%fusion.2 = bf16[8]{0} fusion(%b)", 55, 10, body + "attention/flash/pallas_call"),
           ("%fusion.3 = f32[8]{0} fusion(%c)", 75, 15, body + "norm/rsqrt"),
           ("%fusion.4 = f32[8]{0} fusion(%d)", 150, 30,
            "jit(train_step)/transpose(jvp(lm_head))/add_any"),
           ("%fusion.5 = f32[8]{0} fusion(%e)", 180, 10, "jit(train_step)/optimizer/mul"),
           ("%fusion.6 = f32[8]{0} fusion(%f)", 250, 10, body + "embed/gather")]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": main},
                                        {"name": "python3", "events": producer}]},
        {"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops", "events": [[n, s, d] for n, s, d, _ in ops],
            "op_names": [path for *_, path in ops]}]},
    ]


def test_scope_time_is_self_time_by_scope():
    r = xtrace.reduce(_planes())
    # the loop's 40 less its body's 30 is the loop's own, under no scope;
    # attention holds the flash op inside it; the embed op lies outside
    # the window
    assert {k: r["scopes"].get(k) for k in (
        "mlp", "attention", "flash", "norm", "lm_head", "optimizer", "embed")} == \
        pytest.approx({"mlp": 20e-9, "attention": 10e-9, "flash": 10e-9, "norm": 15e-9,
                       "lm_head": 30e-9, "optimizer": 10e-9, "embed": None})
    # every op in the window carries the jit's name
    assert r["scopes"]["train_step"] == pytest.approx(r["busy_s"])
    assert r["scopes"]["while"] == pytest.approx(10e-9 + 20e-9 + 10e-9 + 15e-9)
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    assert r["ops"][xtrace.op_name("%while.1 = (s32[]) while(%t)")] == pytest.approx(10e-9)
    assert r["steps"] == 2
    assert xtrace.scope_ms(r, "mlp") == pytest.approx(10e-6)
    assert xtrace.scope_ms(r, "embed") is None  # no embed op ran in the window


def test_gaps_take_the_window_threads_innermost_span():
    r = xtrace.reduce(_planes())
    r.update(xscope.gaps(_planes(), long_s=50e-9))
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
    r = xtrace.reduce(_planes())
    assert {k: v["count"] for k, v in r["spans"].items()} == {
        "window": 1, "train": 2, "data": 1, "step": 2, "dispatch": 1,
        "loader/wait": 2, "step/dispatch": 2, "step/block": 2, "step/retry": 1,
        "loader/produce": 2}
    assert r["spans"]["loader/wait"]["s"] == pytest.approx(38e-9)
    assert r["spans"]["loader/produce"]["s"] == pytest.approx(96e-9)
    assert r["spans"]["step/dispatch"]["s"] == pytest.approx(17e-9)


def test_the_benchmarks_numbers_are_xtraces():
    """The program's spans and op names change none of the numbers the
    benchmark's spans alone give."""
    planes = _planes()
    r = xtrace.reduce(planes)
    bare = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [e for e in ln["events"]
                                        if e[0] in xtrace.HOST_SPANS
                                        or p["name"].startswith("/device:")]}
        for ln in p["lines"]]} for p in planes]
    base = xtrace.reduce(bare)
    for key in ("busy_s", "window_s", "devices", "device_ops", "ops", "idle_gaps",
                "idle_by_span", "steps"):
        assert r[key] == base[key], key
    assert base["scopes"] == {}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/mlp/...d,dgf->...gf/dot_general", "mlp"),
    ("jit(train_step)/transpose(jvp(lm_head))/add_any", "lm_head"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/norm/rsqrt", "norm"),
    ("jit(train_step)/optimizer/jit(clip)", "optimizer"),
    ("jit(train_step)/jvp()/while/body/closed_call/attention/jit(_where)/select_n", "attention"),
    ("jit(train_step)/jvp()/while/body/closed_call/attention/flash/pallas_call", "flash"),
    ("jit(train_step)/jvp()/while/body/closed_call/attention/flash/pallas_call", "attention"),
    ("jit(train_step)/jvp()/while/body/dynamic_slice", "while"),
])
def test_scope_names_hold_every_name_on_the_path(op_name, scope):
    assert scope in xtrace.scope_names(op_name)
    assert "/" not in "".join(xtrace.scope_names(op_name))


def test_an_op_without_op_name_has_no_scope():
    assert xtrace.scope_names("") == frozenset()


def test_op_names_read_compiled_hlo():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlp"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("norm"):
            return (y / jnp.sum(y)).sum()

    text = jax.jit(jax.grad(f)).lower(jnp.ones((8, 8))).compile().as_text()
    names = xtrace.op_names(text)
    assert names
    assert set().union(*map(xtrace.scope_names, names.values())) >= {"mlp", "norm"}
    line = next(ln for ln in text.splitlines() if "op_name=" in ln and " = " in ln)
    inst = line.split(" = ")[0].split()[-1].lstrip("%")
    assert inst in names


def test_a_trace_recorded_on_the_chip_keeps_its_numbers():
    """A trace recorded before the program had spans or scopes reduces to
    the numbers it always gave."""
    planes = json.loads(gzip.decompress(
        (TESTDATA / "qwen2_train_trace.json.gz").read_bytes()))
    r = xtrace.reduce(planes)
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
    assert r["scopes"] == {}
    assert set(r["spans"]) <= set(xtrace.HOST_SPANS)


def test_a_scoped_trace_recorded_on_the_chip():
    """Two steps of ``qwen2-0.5b.train_b8s1024`` on a TPU v5e. Each op's
    ``op_names`` entry holds the innermost of the step's named scopes that
    its op_name carried (empty for none), op names shortened to
    ``xtrace.op_name``'s form. Busy time, window, ops and gaps are those
    the benchmark's spans alone give, and the scopes those of the join."""
    planes = json.loads(gzip.decompress(
        (TESTDATA / "qwen2_train_scoped_trace.json.gz").read_bytes()))
    r = xtrace.reduce(planes)
    assert r["busy_s"] == pytest.approx(0.675197687, rel=1e-12)
    assert r["window_s"] == pytest.approx(0.6898798030000001, rel=1e-12)
    assert r["device_ops"] == [[n, pytest.approx(t, rel=1e-12)] for n, t in [
        ["fusion.530 bf16[8,1024,2,4864]", 0.04072954],
        ["fusion.494 bf16[8,1024,2,4864]", 0.038195265],
        ["fusion.496 f32[896]", 0.038109422000000004],
        ["fusion.505 bf16[1,896,2,4864]", 0.037689360000000005],
        ["multiply_reduce_fusion.5 bf16[8,14,1024]", 0.034811258000000005],
        ["fusion.500 bf16[8,14,1024,64]", 0.034351221],
        ["convert_bitcast_fusion.2 f32[8,1024,14,64]", 0.033236224],
        ["fusion.227 f32[896]", 0.027318638000000003],
        ["fusion.207 bf16[152064,896]", 0.026447822000000003],
        ["fusion.181 f32[8,1024]", 0.024544036]]]
    assert r["idle_gaps"][:3] == [[n, pytest.approx(t, rel=1e-12)] for n, t in [
        ["step", 0.011734153], ["step", 0.001557023], ["dispatch", 0.0013772300000000001]]]
    assert r["idle_by_span"] == pytest.approx(
        {"dispatch": 0.001377233, "step": 0.013304883000000002}, rel=1e-12)
    assert {k: r["scopes"][k] for k in ("attention", "mlp", "lm_head", "optimizer")} == \
        pytest.approx({"attention": 0.25137089999999723, "mlp": 0.24683544600000004,
                       "lm_head": 0.101167957, "optimizer": 0.045370016000000006}, rel=1e-12)
    assert sum(r["scopes"].values()) < r["busy_s"]
    assert r["steps"] == 2
    assert {k: v["count"] for k, v in r["spans"].items()
            if xtrace.PROGRAM_SPAN.match(k)} == {
        "loader/wait": 2, "loader/produce": 2, "step/dispatch": 2, "step/block": 2}
    # the host was in a program span on the window's thread at every gap
    assert {n for n, _ in xscope.gaps(planes)["idle_gaps"]} <= {"step/dispatch", "step/block"}
    assert xtrace.scope_ms(r, "attention") == pytest.approx(125.68545, rel=1e-6)
