"""CPU tests of the chip benchmark: registry, result line, checks, faults.

Run from the checkout root: ``python3 -m pytest chipbench/tests``. The
runs here use reduced widths on the CPU, with the harness's look for a
chip bypassed inside the test alone.
"""

from __future__ import annotations

import contextlib
import gzip
import io
import json
import math
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import checks, flops, peaks, registry, run, xtrace  # noqa: E402
from chipbench import weights as W  # noqa: E402
from chipbench.reference import train as reference  # noqa: E402

CELL = "qwen2-0.5b.train_b8s1024"
TESTDATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"

TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=500)
BOTH = ["qwen2-0.5b.train_b8s1024", "qwen2-0.5b.train_b2s4096"]
SCOPE_READERS = ["attention_ms.train", "mlp_ms.train", "lm_head_ms.train",
               "optimizer_ms.train", "flash_attention_roofline"]

# A reference module for an architecture the benchmark does not have: one
# causal attention head of width d over a tied embedding.
TOY = '''
import jax
import jax.numpy as jnp

from chipbench import weights as W


def leaf_specs(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dt = W.DTYPES[cfg["torch_dtype"]]
    specs = {"embed/table": ((v, d), dt, ("normal", 0.02))}
    specs.update({f"layer/w{x}": ((d, d), dt, ("normal", 0.02)) for x in "qkvo"})
    return specs


def param_count(cfg):
    return cfg["vocab_size"] * cfg["hidden_size"] + 4 * cfg["hidden_size"] ** 2


def mixer_flops_per_token(cfg, seq):
    return 4.0 * cfg["hidden_size"] * (seq + 1) / 2


def loss(params, tokens, labels, cfg, dot):
    table, p = params["embed"]["table"], params["layer"]
    x = table[tokens]
    s = tokens.shape[1]
    q, k, v = (dot("bsd,de->bse", x, p[w]) for w in ("wq", "wk", "wv"))
    scores = dot("bsd,btd->bst", q, k) / jnp.sqrt(jnp.float32(x.shape[-1]))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    x = x + dot("bse,ed->bsd", dot("bst,btd->bsd", probs, v), p["wo"])
    logits = dot("bsd,vd->bsv", x, table)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
'''


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def _tiny_root(tmp_path: pathlib.Path, cell: str = CELL,
               limits=None) -> pathlib.Path:
    """A registry with one reduced-width cell named ``tiny.train``."""
    root = tmp_path / "bench"
    shutil.copytree(registry.ROOT / "metrics", root / "metrics")
    for d in ("configs", "traffic", "workloads"):
        (root / d).mkdir(parents=True)
    cfg = registry.config(registry.workload(cell)["config"])
    cfg.update(TINY)
    (root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    tr = registry.traffic(registry.workload(cell)["traffic"])
    tr.update(batch=4, seq=64, ref_rows_per_block=2)
    (root / "traffic" / "tiny.json").write_text(json.dumps(tr))
    cell = dict(registry.workload(cell), config="tiny", traffic="tiny")
    if limits is not None:
        cell["limits"] = limits
    (root / "workloads" / "tiny.train.json").write_text(json.dumps(cell))
    bench = registry.benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.train"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run_tiny(root: pathlib.Path, monkeypatch, seed: int = 2**31 + 7):
    monkeypatch.setattr(run, "require_chip", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", "tiny.train", "--seed", str(seed),
                       "--seconds", "1", "--trace", "0"], root=root, repo=root)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


# --- registry -------------------------------------------------------------

def test_new_cell_is_found_by_name(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(registry.ROOT / "workloads", root / "workloads")
    before = registry.workload_names(root)
    (root / "workloads" / "new-model.train_x.json").write_text(json.dumps(
        {"config": "new-model", "traffic": "train_x", "chips": 1, "why": "w",
         "limits": {}}))
    assert registry.workload_names(root) == sorted(before + ["new-model.train_x"])
    assert registry.workload("new-model.train_x", root)["traffic"] == "train_x"


def test_benchmark_names_resolve_to_files():
    bench = registry.benchmark()
    for c in bench["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in bench["workloads"]:
        cell = registry.workload(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        registry.driver(registry.traffic(w["traffic"])["driver"])
    for m in bench["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


# --- the harness on a CPU -------------------------------------------------

def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    cmd = registry.benchmark()["command"]
    p = subprocess.run([sys.executable, *cmd[1:], "--workload", CELL,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_tiny_run_prints_the_contract_line(tmp_path, monkeypatch):
    res = _run_tiny(_tiny_root(tmp_path), monkeypatch)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device",
                         "checks"]
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == set(registry.workload(CELL)["limits"])


def test_the_window_waits_for_every_step_it_sent(tmp_path):
    """Steps run ahead of the host; every one sent is done and counted when
    the window's clock stops."""
    import time
    from types import SimpleNamespace
    from chipbench.drivers import train_loop

    root = _tiny_root(tmp_path)
    ctx = SimpleNamespace(workload=registry.workload("tiny.train", root),
                          config=registry.config("tiny", root),
                          traffic=registry.traffic("tiny", root), seed=3,
                          seconds=1.0, trace=False, trace_dir=str(tmp_path / "t"),
                          t0=time.perf_counter())
    out = train_loop.run(ctx)
    log = out["log"]
    assert log["ahead"] > 1
    assert log["state_step"] == ctx.traffic["check_steps"] + out["attempted"]
    assert out["attempted"] == log["window_steps"]
    assert out["tokens_per_s"] == pytest.approx(
        log["window_steps"] * 4 * 64 / log["window_s"])


def _broken_step(kind):
    from repro.train import step as step_lib
    real = step_lib.make_train_step

    def make(model, opt_cfg, accum=1):
        step = real(model, opt_cfg, accum)

        def broken(state, batch):
            if kind == "half_batch":
                half = batch["tokens"].shape[0] // 2
                return step(state, {k: v[:half] for k, v in batch.items()})
            new, metrics = step(state, batch)
            return state, metrics  # the state comes back unchanged
        return broken
    return make


@pytest.mark.parametrize("kind", ["unchanged_state", "half_batch"])
def test_a_broken_step_is_not_correct(kind, tmp_path, monkeypatch):
    from repro.train import step as step_lib
    monkeypatch.setattr(step_lib, "make_train_step", _broken_step(kind))
    res = _run_tiny(_tiny_root(tmp_path), monkeypatch)
    assert res["correct"] is False


def test_the_control_is_not_correct():
    """The reference in int8 in the program's place fails the cell's limits."""
    cfg = registry.config("qwen2-0.5b")
    cfg.update(TINY)
    tr = registry.traffic(registry.workload(CELL)["traffic"])
    tr.update(batch=4, seq=64, ref_rows_per_block=2)
    limits = registry.workload(CELL)["limits"]
    ref = reference.run(cfg, tr, 5, "highest")
    for seed in (5,):
        ctl = reference.run(cfg, tr, seed, "int8")
        assert not checks.judge(checks.numbers(ctl, ref), limits)


def test_hbm_share_counts_the_steps_temporaries(tmp_path):
    """The footprint the reader divides holds more than the step's state."""
    import jax
    from chipbench.drivers import train_loop

    root = _tiny_root(tmp_path)
    cfg, tr = registry.config("tiny", root), registry.traffic("tiny", root)
    s = train_loop.setup(cfg, tr, 11)
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(s.state))
    footprint = train_loop.step_footprint(
        s.trainer.jstep.lower(s.state, next(s.loader)).compile())
    s.loader.close()
    assert footprint > state_bytes
    read = registry.metric_reader("hbm_peak_frac.train")
    assert read({"step_bytes": footprint, "bytes_limit": 4 * footprint}) == 25.0
    assert read({"step_bytes": None, "bytes_limit": 4 * footprint}) is None


# --- the reference against the program -----------------------------------

@pytest.mark.parametrize("law", ["zipf", "uniform"])
def test_reference_loss_matches_the_program_in_fp32(law, tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    from chipbench.drivers import train_loop
    from chipbench.reference import precision, qwen2

    cfg = registry.config("tiny", _tiny_root(tmp_path))
    cfg["torch_dtype"] = "float32"
    model = build_model(train_loop.program_config(cfg))
    params = W.make_params(W.leaf_specs(cfg), W.seed_key(3))
    batch = W.Tokens(cfg["vocab_size"], 2, 64, 3, law).batch_at(0)
    with jax.default_matmul_precision("highest"):
        prog = float(model.loss(params, {k: jnp.asarray(v) for k, v in batch.items()})[0])
    ref = float(qwen2.loss(params, batch["tokens"], batch["labels"], cfg,
                           precision.highest))
    assert abs(prog - ref) <= 1e-5 * abs(ref)


def test_weights_take_the_programs_layout():
    import jax
    from repro.models import build_model
    from chipbench.drivers import train_loop

    cfg = registry.config("qwen2-0.5b")
    model = build_model(train_loop.program_config(cfg))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.init_shape())
    have = jax.eval_shape(lambda k: W.make_params(W.leaf_specs(cfg), k),
                          W.seed_key(1))
    assert jax.tree.map(lambda s: (s.shape, s.dtype), have) == want


def test_seeds_wider_than_32_bits_differ():
    import jax
    a, b = W.seed_key(2**33 + 5), W.seed_key(5)
    assert (jax.random.key_data(a) != jax.random.key_data(b)).any()
    for law in ("zipf", "uniform"):
        rows = W.Tokens(1000, 4, 16, 2**33 + 5, law).batch_at(0)["tokens"]
        assert len({r.tobytes() for r in rows}) == 4
        again = W.Tokens(1000, 4, 16, 2**33 + 5, law).batch_at(0)["tokens"]
        assert (rows == again).all()
        assert rows.min() >= 0 and rows.max() < 1000


def test_uniform_rows_name_few_ids_twice():
    cfg = registry.config("qwen2-0.5b")
    zipf = registry.traffic("train_b8s1024")
    for tr in (zipf, dict(zipf, tokens="uniform")):
        rows = W.tokens(cfg, tr, 7).batch_at(0)["tokens"]
        top = max(__import__("collections").Counter(rows.ravel().tolist()).values())
        if tr["tokens"] == "uniform":
            assert top <= 4  # no id fills many positions of a step
        else:
            assert top > rows.size / 10  # the first Zipf id fills a fifth


# --- yardstick arithmetic -------------------------------------------------

def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_six_n_is_the_programs_parameter_count():
    from repro.models import build_model
    from chipbench.drivers import train_loop

    cfg = registry.config("qwen2-0.5b")
    mcfg = train_loop.program_config(cfg)
    d = mcfg.d_model
    padding = (W.padded_vocab(cfg["vocab_size"]) - cfg["vocab_size"]) * d
    n = build_model(mcfg).param_count(active_only=True) - padding
    assert flops.param_count(cfg) == n


def test_mixer_flops_by_hand():
    q = registry.config("qwen2-0.5b")
    # 4 * 24 layers * 14 heads * 64 * (1024 + 1) / 2
    assert flops.mixer_flops_per_token(q, 1024) == 44_083_200
    assert flops.train_flops_per_token(q, 1024) == 6 * flops.param_count(q) + 3 * 44_083_200


def test_qwen2_leaves_keep_their_order():
    """A leaf's draw is ``fold_in(key, its index among the sorted paths)``:
    the same paths give the same weights."""
    assert sorted(W.leaf_specs(registry.config("qwen2-0.5b"))) == [
        "embed/table", "final_norm/scale",
        "layers/L0/mixer/bk", "layers/L0/mixer/bq", "layers/L0/mixer/bv",
        "layers/L0/mixer/wk", "layers/L0/mixer/wo", "layers/L0/mixer/wq",
        "layers/L0/mixer/wv", "layers/L0/mlp/wi", "layers/L0/mlp/wo",
        "layers/L0/norm1/scale", "layers/L0/norm2/scale"]


# --- a configuration added by files alone ---------------------------------

def _toy_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A registry with a reference module, configuration, traffic and cell
    of its own, and the benchmark's readers."""
    root = tmp_path / "bench"
    shutil.copytree(registry.ROOT / "metrics", root / "metrics")
    for d in ("configs", "traffic", "workloads", "reference"):
        (root / d).mkdir(parents=True)
    (root / "reference" / "toy.py").write_text(TOY)
    (root / "configs" / "toy-1l.json").write_text(json.dumps(
        {"reference": "toy", "hidden_size": 32, "vocab_size": 300,
         "torch_dtype": "bfloat16"}))
    tr = dict(registry.traffic("train_b8s1024"), batch=2, seq=16, ref_rows_per_block=1)
    (root / "traffic" / "toy_b2s16.json").write_text(json.dumps(tr))
    (root / "workloads" / "toy-1l.train.json").write_text(json.dumps(
        {"config": "toy-1l", "traffic": "toy_b2s16", "chips": 1, "why": "w",
         "limits": {}}))
    bench = registry.benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["toy-1l.train"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_configuration_is_added_by_files(tmp_path):
    root = _toy_root(tmp_path)
    cfg = registry.config("toy-1l", root)
    tr = registry.traffic("toy_b2s16", root)
    assert sorted(W.leaf_specs(cfg)) == ["embed/table", "layer/wk", "layer/wo",
                                         "layer/wq", "layer/wv"]
    n = 300 * 32 + 4 * 32 * 32
    assert flops.train_flops_per_token(cfg, 16) == 6 * n + 3 * 4 * 32 * 17 / 2
    ref = reference.run(cfg, tr, 2**31 + 3, steps=2)
    assert all(abs(x - math.log(300)) < 0.5 for x in ref["loss"])
    assert set(ref["change"]) == set(W.leaf_specs(cfg))

    trace = {"busy_s": 0.9, "window_s": 1.0, "devices": 1, "steps": 4,
             "scopes": {"attention": 0.004, "flash": 0.002, "mlp": 0.008,
                        "lm_head": 0.012, "optimizer": 0.016}}
    out = {"trace": trace, "config": cfg, "traffic": tr, "device_kind": "TPU v5 lite",
           "flops_per_token": flops.train_flops_per_token(cfg, 16),
           "tokens_per_s": 1e6, "step_bytes": 1, "bytes_limit": 4}
    got = run.per_layer("toy-1l.train", out, registry.benchmark(root), root)
    assert set(got) == {m["name"] for m in registry.benchmark()["per_layer"]}
    assert {k: got[k]["value"] for k in SCOPE_READERS} == pytest.approx({
        "attention_ms.train": 1.0, "mlp_ms.train": 2.0, "lm_head_ms.train": 3.0,
        "optimizer_ms.train": 4.0,
        # 3 * 4 * 32 * 17 / 2 FLOPs a token, 2 * 16 tokens a step, 4 steps
        "flash_attention_roofline": 100 * 3 * 1088 * 32 * 4 / (0.002 * 197e12)})


def test_the_harness_names_no_architecture():
    from repro.configs import list_archs

    names = {re.match(r"[a-z]+", a).group(0) for a in list_archs()}
    names |= {p.stem for p in (registry.ROOT / "reference").glob("*.py")} - {
        "__init__", "train", "precision"}
    names |= {p.stem for p in (registry.ROOT / "configs").glob("*.json")}
    files = [registry.ROOT / f for f in ("weights.py", "flops.py", "run.py",
                                         "xtrace.py", "xscope.py")]
    files += sorted((registry.ROOT / "drivers").glob("*.py"))
    for f in files:
        text = f.read_text().lower()
        for name in names:
            assert not re.search(rf"(?<![a-z]){re.escape(name)}", text), (f.name, name)


# --- per-layer readers ----------------------------------------------------

def _flash_planes(flash_ns, steps):
    """A hand-made trace: ``steps`` steps, each with one attention
    projection and one kernel call under ``flash`` of ``flash_ns``."""
    body = "jit(train_step)/jvp()/while/body/closed_call/attention/"
    host = [["window", 0, 1e9]]
    events, names = [], []
    for i in range(steps):
        t = i * 1e9 / steps
        host.append(["train", t, 1e9 / steps])
        events += [[f"%fusion.{i} = bf16[8]{{0}} fusion(%a)", t, 1e6],
                   [f"%custom-call.{i} = bf16[8]{{0}} custom-call(%q)", t + 2e6, flash_ns]]
        names += [body + "dot_general", body + "flash/pallas_call"]
    return [{"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
            {"name": "/device:TPU:0", "lines": [
                {"name": "XLA Ops", "events": events, "op_names": names}]}]


def test_flash_roofline_by_hand():
    cfg = registry.config("qwen2-0.5b")
    tr = registry.traffic("train_b2s4096")
    r = xtrace.reduce(_flash_planes(flash_ns=50e6, steps=2))
    out = {"trace": r, "config": cfg, "traffic": tr, "device_kind": "TPU v5 lite"}
    read = registry.metric_reader("flash_attention_roofline")
    # 4 * 24 layers * 14 heads * 64 * (4096 + 1) / 2 = 176203776 forward
    # FLOPs a token, three times over 2 x 4096 tokens a step, 2 steps, in
    # 2 x 50 ms against 197 TFLOP/s
    assert read(out) == pytest.approx(100 * 3 * 176_203_776 * 8192 * 2 / (0.1 * 197e12))
    assert 40 < read(out) < 45
    ms = registry.metric_reader("attention_ms.train")(out)
    assert ms == pytest.approx(51.0)  # the projection's 1 ms and the kernel's 50
    planes = _flash_planes(flash_ns=50e6, steps=2)
    planes[1]["lines"][0]["op_names"] = [n.replace("flash/", "")
                                         for n in planes[1]["lines"][0]["op_names"]]
    out["trace"] = xtrace.reduce(planes)
    assert read(out) is None
    assert registry.metric_reader("attention_ms.train")(out) == pytest.approx(51.0)


def test_readers_on_a_scoped_trace_recorded_on_the_chip():
    """Two steps of b8s1024 on a TPU v5e, recorded with no ``flash``
    scope: every reader but the kernel's reads."""
    planes = json.loads(gzip.decompress(
        (TESTDATA / "qwen2_train_scoped_trace.json.gz").read_bytes()))
    out = {"trace": xtrace.reduce(planes), "config": registry.config("qwen2-0.5b"),
           "traffic": registry.traffic("train_b8s1024"), "device_kind": "TPU v5 lite"}
    got = {n: registry.metric_reader(n)(out) for n in SCOPE_READERS}
    assert got == pytest.approx({
        "attention_ms.train": 125.68545, "mlp_ms.train": 123.41772300000002,
        "lm_head_ms.train": 50.5839785, "optimizer_ms.train": 22.685008000000003,
        "flash_attention_roofline": None}, rel=1e-9)


def test_benchmark_lists_the_readers_in_both_cells():
    bench = registry.benchmark()
    layers = {m["name"]: m for m in bench["per_layer"]}
    for name in SCOPE_READERS:
        m = layers[name]
        assert m["workloads"] == BOTH and m["moves"] == "train_tokens_per_s"
        assert m["source"] == "device_trace"
    assert layers["flash_attention_roofline"]["layer"] == "kernels"
    assert {layers[n]["layer"] for n in SCOPE_READERS[:4]} == {"model step"}


# --- trace reduction ------------------------------------------------------

def test_reduce_a_hand_made_trace():
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["window", 0, 100], ["data", 10, 10], ["step", 20, 70],
            ["dispatch", 20, 5]]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%while.1 = (s32[], bf16[8]{0}) while(%t)", 25, 40],
            ["%fusion.1 = bf16[8]{0:T(8)} fusion(%a)", 30, 20],
            ["%fusion.2 = f32[2]{0} fusion(%b)", 50, 10],
            ["%copy.3 = f32[2]{0} copy(%c)", 80, 10],
            ["%outside = f32[2]{0} copy(%c)", 200, 50]]}]},
    ]
    r = xtrace.reduce(planes)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)  # [25, 65) and [80, 90)
    # self time: the loop's 40 less its body's 30
    assert dict(r["device_ops"]) == pytest.approx({
        "fusion.1 bf16[8]": 20e-9, "fusion.2 f32[2]": 10e-9,
        "while.1 s32[]": 10e-9, "copy.3 f32[2]": 10e-9})
    # gaps: [0, 25) in "data" at its middle, [65, 80) in "step", [90, 100)
    # in no span
    assert r["idle_gaps"][0] == ["data", pytest.approx(25e-9)]
    assert r["idle_by_span"] == pytest.approx(
        {"data": 25e-9, "step": 15e-9, "host": 10e-9})


def test_reduce_a_trace_recorded_on_the_chip():
    planes = json.loads(gzip.decompress((TESTDATA / "qwen2_train_trace.json.gz").read_bytes()))
    r = xtrace.reduce(planes)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["devices"] == 1
    assert r["device_ops"] and r["idle_gaps"]
    assert all(n in xtrace.HOST_SPANS + ("host",) for n, _ in r["idle_gaps"])
