"""CPU tests of the Moonlight-16B-A3B cell: its files, its model FLOPs, and
the program against the plain DeepSeek-V3 reference at toy widths.

Run from the checkout root: ``python3 -m pytest chipbench/tests``. The
runs here use reduced widths on the CPU, with the harness's look for a
chip bypassed inside the test alone.
"""

from __future__ import annotations

import contextlib
import io
import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import flops, registry, run  # noqa: E402
from chipbench import weights as W  # noqa: E402

CELL = "moonlight-16b-a3b.train_b2s8192"
# Moonlight's block at toy widths: 1 dense + 2 expert layers, 4 of 8
# experts held, top 2, q/k heads of 16 + 8, v heads of 16
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=4, num_experts_per_tok=2, num_hidden_layers=3,
            vocab_size=512)


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def _tiny_root(tmp_path: pathlib.Path) -> pathlib.Path:
    """A registry with the cell at ``TINY`` widths, named ``tiny.train``;
    the router keeps twice the experts held."""
    root = tmp_path / "bench"
    shutil.copytree(registry.ROOT / "metrics", root / "metrics")
    for d in ("configs", "traffic", "workloads"):
        (root / d).mkdir(parents=True)
    cell = registry.workload(CELL)
    cfg = registry.config(cell["config"])
    cfg.update(TINY)
    cfg["reduced"]["n_routed_experts"]["published"] = 2 * TINY["n_routed_experts"]
    (root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    tr = registry.traffic(cell["traffic"])
    tr.update(batch=4, seq=64, ref_rows_per_block=2)
    (root / "traffic" / "tiny.json").write_text(json.dumps(tr))
    (root / "workloads" / "tiny.train.json").write_text(json.dumps(
        dict(cell, config="tiny", traffic="tiny")))
    bench = registry.benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.train"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_files_load():
    from chipbench.drivers import train_loop

    cell = registry.workload(CELL)
    cfg = registry.config(cell["config"])
    mcfg = train_loop.program_config(cfg)
    assert (mcfg.num_experts, mcfg.held_experts, mcfg.top_k) == (64, 8, 6)
    assert (mcfg.num_layers, mcfg.first_dense_layers, mcfg.vocab_size) == (5, 1, 20480)
    assert mcfg.moe_scoring == "sigmoid" and mcfg.norm_eps == 1e-5
    tr = registry.traffic(cell["traffic"])
    assert (tr["batch"], tr["seq"], tr["tokens"]) == (2, 8192, "uniform")
    # every width as published; the three cut keys name their published size
    assert {k: v["published"] for k, v in cfg["reduced"].items()} == {
        "num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840}
    bench = {c["name"]: c for c in registry.benchmark()["configs"]}
    assert sorted(bench["moonlight-16b-a3b"]["reduced"]) == sorted(cfg["reduced"])


def test_the_cell_reports_a_rate_and_per_layer_metrics():
    bench = registry.benchmark()
    e2e = [m["name"] for m in registry.metrics_of(CELL, bench["end_to_end"])]
    assert e2e == ["train_tokens_per_s", "setup_s"]
    layers = [m["name"] for m in registry.metrics_of(CELL, bench["per_layer"], e2e)]
    assert layers == ["mfu.train", "device_idle.train", "hbm_peak_frac.train"]
    for name in layers:
        assert registry.metric_reader(name)  # each has its reader file


def test_weights_take_the_programs_layout():
    import jax
    from repro.models import build_model
    from chipbench.drivers import train_loop

    cfg = registry.config("moonlight-16b-a3b")
    model = build_model(train_loop.program_config(cfg))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.init_shape())
    have = jax.eval_shape(lambda k: W.make_params(W.leaf_specs(cfg), k),
                          W.seed_key(1))
    assert jax.tree.map(lambda s: (s.shape, s.dtype), have) == want


def test_model_flops_by_hand():
    from repro.models import build_model
    from chipbench.drivers import train_loop

    cfg = registry.config("moonlight-16b-a3b")
    # the active parameters at this chip's share, the input table left out
    assert flops.param_count(cfg) == 275_669_760
    model = build_model(train_loop.program_config(cfg))
    table = cfg["vocab_size"] * cfg["hidden_size"]
    assert flops.param_count(cfg) == model.param_count(active_only=True) - table
    # 2 * 5 layers * 16 heads * (192 + 128) * (8192 + 1) / 2
    assert flops.mixer_flops_per_token(cfg, 8192) == 209_740_800
    per_token = flops.train_flops_per_token(cfg, 8192)
    assert per_token == pytest.approx(2.283e9, rel=1e-3)


@pytest.mark.parametrize("blocks", [None, (16, 32)])
def test_program_matches_the_reference(blocks, tmp_path, monkeypatch):
    """Loss and every weight's gradient at toy widths, both sides fp32 on
    the same seeded weights and uniform rows; with ``blocks`` the
    reference's attention and per-token work run in blocks of 16 query
    rows and 32 token rows."""
    import jax
    import jax.numpy as jnp
    from repro.models import build_model
    from chipbench.drivers import train_loop
    from chipbench.reference import deepseek_v3, precision

    if blocks:
        monkeypatch.setattr(deepseek_v3, "Q_BLOCK", blocks[0])
        monkeypatch.setattr(deepseek_v3, "ROW_BLOCK", blocks[1])
    cfg = registry.config("tiny", _tiny_root(tmp_path))
    cfg["torch_dtype"] = "float32"
    model = build_model(train_loop.program_config(cfg))
    specs = W.leaf_specs(cfg)
    params = W.make_params(specs, W.seed_key(5))
    want = jax.tree.map(lambda s: (s.shape, s.dtype), model.init_shape())
    assert jax.tree.map(lambda s: (s.shape, s.dtype), params) == want
    batch = W.Tokens(cfg["vocab_size"], 2, 64, 5, "uniform").batch_at(0)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    with jax.default_matmul_precision("highest"):
        prog, g_prog = jax.value_and_grad(lambda p: model.loss(p, jb)[0])(params)
    ref, g_ref = jax.value_and_grad(deepseek_v3.loss)(
        params, batch["tokens"], batch["labels"], cfg, precision.highest)
    assert abs(float(prog) - float(ref)) <= 1e-5 * abs(float(ref))
    flat_p, flat_r = W.flatten(g_prog), W.flatten(g_ref)
    assert set(flat_p) == set(specs)
    for path in specs:
        a, b = flat_p[path], flat_r[path]
        scale = float(jnp.max(jnp.abs(b)))
        if path.endswith("router_bias"):
            assert scale == 0.0 and float(jnp.max(jnp.abs(a))) == 0.0
            continue
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, path


def test_tiny_run_is_correct(tmp_path, monkeypatch):
    root = _tiny_root(tmp_path)
    monkeypatch.setattr(run, "require_chip", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", "tiny.train", "--seed", str(2**31 + 7),
                       "--seconds", "1", "--trace", "0"], root=root, repo=root)
    assert rc == 0
    res = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True
    assert set(res["checks"]) == set(registry.workload(CELL)["limits"])
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
