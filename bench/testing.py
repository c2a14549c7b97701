"""A tiny cell for the harness's own tests: a copy of the benchmark in a
temporary checkout, with a two-layer model, a backlog mix at toy sizes and
its cell limits added as new files and entries, as a later change would
add them. It runs on the CPU in seconds."""
from __future__ import annotations

import argparse
import json
import os
import shutil

from bench import spec

TINY = "tiny.batch"


def tiny_root(tmp: str) -> str:
    root = os.path.join(tmp, "checkout")
    shutil.copytree(spec.BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    os.symlink(os.path.join(spec.ROOT, "src"), os.path.join(root, "src"))
    b = os.path.join(root, "bench")
    conf = spec._json(os.path.join(b, "configs", "qwen3-4b.json"))
    conf.update({"num_hidden_layers": 2, "hidden_size": 64,
                 "num_attention_heads": 4, "num_key_value_heads": 2,
                 "head_dim": 16, "intermediate_size": 128,
                 "vocab_size": 500,
                 "engine": {"block_size": 16, "num_blocks": 96,
                            "prefill_chunk": 32}})
    mix = {
        "kind": "backlog", "backlog_depth": 8, "backlog_requests_per_s": 4,
        "block": 10, "stratum": 2,
        "prompt_tokens": {"dist": "lognormal", "median": 40, "sigma": 0.5,
                          "min": 20, "max": 90},
        "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                          "min": 3, "max": 12},
        "max_batch": 4, "lead_in_s": 1.0}
    files = {
        "configs/tiny.json": conf,
        "traffic/tiny-batch.json": mix,
        f"cells/{TINY}.json": {"check": {"gap_max": 0.1,
                                         "sample_tokens": 30,
                                         "sample_requests": 12}},
    }
    for rel, obj in files.items():
        with open(os.path.join(b, rel), "w") as f:
            json.dump(obj, f)
    bm = spec.benchmark()
    bm["configs"].append({"name": "tiny", "source": "test",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"})
    bm["workloads"].append({"name": TINY, "config": "tiny",
                            "traffic": "tiny-batch", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "qwen3-4b.batch-backlog" in m.get("workloads", [TINY]):
            m["workloads"].append(TINY)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return root


def args(seed: int = 1, seconds: float = 2.0, trace: int = 0):
    return argparse.Namespace(workload=TINY, seed=seed, seconds=seconds,
                              trace=trace)
