"""Shared profile-data collection with an on-disk cache.

The paper collects 17,300 + 5,500 data points across days of GPU time; on
one host the benchmark suite collects a scaled-down set (a few hundred
points; BENCH_FULL=1 widens the grid) once, cached in
``artifacts/profiles.jsonl`` keyed by configuration, by the device it
was measured on and by the attention path its steps took there, so every
MRE benchmark reads the same corpus and never another device's labels. A profile that fails stops the collection.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Tuple

from repro.core.features import (ProfileRecord, record_from_json,
                                 record_to_json)

CACHE = os.environ.get("REPRO_PROFILE_CACHE", "artifacts/profiles.jsonl")
FULL = bool(int(os.environ.get("BENCH_FULL", "0")))


def bench_candidates(seed: int):
    """Bounded AutoML pool for the 1-core benchmark budget."""
    from repro.core.automl.models import (ExtraTreesRegressor,
                                          GradientBoostingRegressor,
                                          KNNRegressor,
                                          RandomForestRegressor,
                                          RidgeRegressor)
    return [
        RandomForestRegressor(n_trees=40, max_depth=16, max_features=0.6,
                              min_samples_leaf=1, seed=seed),
        ExtraTreesRegressor(n_trees=40, max_depth=16, seed=seed + 1),
        GradientBoostingRegressor(n_stages=160, learning_rate=0.08,
                                  max_depth=4, seed=seed + 2),
        RidgeRegressor(alpha=1.0),
        KNNRegressor(k=3),
    ]

# the 29-network zoo split into profiling tiers (cost grows down the list)
FAST_NETS = ["lenet5", "alexnet", "squeezenet", "nin", "mobilenet_v1",
             "shufflenet_v2", "convmixer_lite", "vgg11", "resnet18",
             "wideresnet16_4", "densenet63"]
MID_NETS = ["vgg13", "vgg16", "resnet34", "se_resnet18", "mobilenet_v2",
            "shufflenet_v1", "googlenet", "preact_resnet18",
            "efficientnet_lite0", "resnext29", "inception_v3_lite",
            "se_resnet34", "stochastic_depth34", "resnet50"]
SLOW_NETS = ["vgg19", "resnet101", "resnet152", "preact_resnet152"]

LM_ARCHS = ["qwen2-0.5b", "chatglm3-6b", "phi4-mini-3.8b", "mamba2-370m",
            "whisper-tiny", "moonlight-16b-a3b", "jamba-v0.1-52b",
            "llama-3.2-vision-90b"]


def zoo_grid() -> List[Dict]:
    combos = []
    batches = (8, 16, 32, 64) if FULL else (8, 32)
    for net in FAST_NETS:
        for b in batches:
            combos.append(dict(kind="zoo", name=net, batch=b, image=32))
        combos.append(dict(kind="zoo", name=net, batch=16, image=24))
        combos.append(dict(kind="zoo", name=net, batch=16, image=32,
                           optimizer="adam"))
    for net in MID_NETS:
        for b in (8, 32) if FULL else (16,):
            combos.append(dict(kind="zoo", name=net, batch=b, image=32))
        combos.append(dict(kind="zoo", name=net, batch=8, image=24))
    for net in SLOW_NETS:
        combos.append(dict(kind="zoo", name=net, batch=8, image=32))
    return combos


def random_grid(n: Optional[int] = None) -> List[Dict]:
    n = n or (60 if FULL else 24)
    out = []
    for seed in range(n):
        out.append(dict(kind="rand_cnn", seed=seed,
                        batch=8 + 8 * (seed % 3), image=32))
    for seed in range(n // 2):
        out.append(dict(kind="rand_lm", seed=seed, batch=2, seq=64))
    return out


def lm_grid() -> List[Dict]:
    out = []
    for arch in LM_ARCHS:
        for b, s in ((2, 64), (4, 128)) if FULL else ((2, 64),):
            out.append(dict(kind="lm", name=arch, batch=b, seq=s))
    return out


def _device() -> Dict[str, str]:
    """The device this process measures on, as JAX reports it, and the
    attention path its steps take there (a label profiled through the
    materialised scores is never mixed with the kernel's)."""
    import jax

    from repro.models.attention import attention_key
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "attention": attention_key()}


def _key(combo: Dict) -> str:
    return json.dumps({**combo, **_device()}, sort_keys=True)


def _load_cache() -> Dict[str, Dict]:
    out = {}
    if os.path.exists(CACHE):
        with open(CACHE) as f:
            for line in f:
                try:
                    d = json.loads(line)
                    out[d["key"]] = d["record"]
                except (json.JSONDecodeError, KeyError):
                    continue
    return out


def _profile(combo: Dict) -> ProfileRecord:
    from repro.core import profiler
    from repro.core.randomgen import random_cnn, random_lm_config
    kind = combo["kind"]
    if kind == "zoo":
        return profiler.profile_zoo(
            combo["name"], batch=combo.get("batch", 16),
            image=combo.get("image", 32), lr=combo.get("lr", 0.1),
            optimizer=combo.get("optimizer", "sgd"), steps=2)
    if kind == "rand_cnn":
        model = random_cnn(combo["seed"])
        import jax
        import jax.numpy as jnp
        import numpy as np
        params = model.init(jax.random.key(0))
        step, init_opt = profiler.zoo_train_step(model, "sgd", 0.1)
        opt_state = init_opt(params)
        sds = lambda t: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        x = jax.ShapeDtypeStruct(
            (combo["batch"], combo["image"], combo["image"], 3), jnp.float32)
        y = jax.ShapeDtypeStruct((combo["batch"],), jnp.int32)
        meas = profiler.profile_step(step, (sds(params), sds(opt_state), x, y),
                                     steps=2)
        n = int(sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params)))
        return ProfileRecord(
            model_name=model.name, family="rand_cnn",
            batch_size=combo["batch"], input_size=combo["image"], channels=3,
            learning_rate=0.1, epoch=1, optimizer="sgd",
            layers=model.layer_count(), flops=meas["flops"], params=n,
            nsm_edges=meas["nsm_edges"], time_s=meas["time_s"],
            mem_bytes=meas["mem_bytes"])
    if kind == "rand_lm":
        cfg = random_lm_config(combo["seed"])
        return profiler.profile_lm(cfg, batch=combo["batch"],
                                   seq=combo["seq"], steps=2)
    if kind == "lm":
        from repro.configs import get_config, reduced_config
        cfg = reduced_config(get_config(combo["name"]))
        return profiler.profile_lm(cfg, batch=combo["batch"],
                                   seq=combo["seq"], steps=2)
    raise ValueError(kind)


def collect(combos: List[Dict], verbose: bool = True) -> List[ProfileRecord]:
    cache = _load_cache()
    os.makedirs(os.path.dirname(CACHE) or ".", exist_ok=True)
    out = []
    for i, combo in enumerate(combos):
        key = _key(combo)
        if key in cache:
            out.append(record_from_json(cache[key]))
            continue
        t0 = time.time()
        try:
            rec = _profile(combo)
        except Exception as e:
            e.add_note(f"[collect] while profiling {key}")
            raise
        with open(CACHE, "a") as f:
            f.write(json.dumps({"key": key,
                                "record": record_to_json(rec)}) + "\n")
        cache[key] = record_to_json(rec)
        out.append(rec)
        if verbose:
            print(f"[collect] {i + 1}/{len(combos)} {combo.get('name', combo.get('seed'))} "
                  f"({time.time() - t0:.0f}s) time={rec.time_s * 1e3:.0f}ms",
                  flush=True)
    return out


def corpus() -> Tuple[List[ProfileRecord], List[ProfileRecord], List[ProfileRecord]]:
    """(zoo_records, random_records, lm_records) — collected or cached."""
    return (collect(zoo_grid()), collect(random_grid()), collect(lm_grid()))


def all_cached() -> List[ProfileRecord]:
    """Every record ever profiled on this device (incl. batch sweeps from
    other benches) — the densest corpus available, closest to the paper's
    17k-point grid."""
    dev = _device()
    return [record_from_json(d) for k, d in _load_cache().items()
            if {f: json.loads(k).get(f) for f in dev} == dev]
