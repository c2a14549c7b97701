"""The benchmark's layout: every piece found by its name, a new cell, mix,
configuration and metric added as new files and entries with no edit to
an existing file, the peaks table, and the refusal to run off a TPU."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec, testing  # noqa: E402

BM = spec.benchmark()


@pytest.mark.parametrize("wl", [w["name"] for w in BM["workloads"]])
def test_each_cell_finds_its_files(wl):
    from bench import run
    c = run.load_cell(wl)
    assert c.wl["traffic"] and c.mix["max_batch"] > 0
    assert {"gap_max", "sample_tokens", "sample_requests"} <= set(c.limits)
    assert hasattr(c.ref, "served_gaps") and hasattr(c.ref, "make_weights")
    reported = {m["name"] for m in spec.metrics_for(BM, wl, False)}
    assert "setup_s" in reported and len(reported) >= 2
    for m in spec.metrics_for(BM, wl, True):
        assert m["moves"] in reported, (m["name"], wl)


@pytest.mark.parametrize("m", [m["name"] for m in BM["end_to_end"]
                               + BM["per_layer"]])
def test_each_metric_has_a_reader_that_agrees(m):
    entry = [x for x in BM["end_to_end"] + BM["per_layer"]
             if x["name"] == m][0]
    rd = spec.reader(m)
    assert callable(rd.read)
    assert rd.UNIT == entry["unit"] and rd.SOURCE == entry["source"]
    if "layer" in entry:
        assert entry["layer"].startswith(rd.LAYER)
        assert rd.MOVES == entry["moves"]


def test_configuration_files_hold_what_they_reduce():
    for entry in BM["configs"]:
        conf = spec.config(BM, entry["name"])
        assert conf["reduced"] == entry["reduced"]
        assert set(conf["source_values"]) == set(entry["reduced"])
        for key in entry["reduced"]:
            assert conf[key] != conf["source_values"][key]


def test_peaks_know_v5e_and_refuse_other_kinds():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_refuses_without_a_tpu():
    """Off a TPU the command exits non-zero and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-4b.batch-backlog", "--seed", "0", "--seconds", "10",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files
    exits non-zero and prints no result line."""
    import shutil
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-4b.batch-backlog", "--seed", "0", "--seconds", "10",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_new_cell_mix_config_and_metric_are_found_by_name(tmp_path):
    root = testing.tiny_root(str(tmp_path))
    before = {os.path.relpath(os.path.join(d, f), root)
              for d, _, fs in os.walk(os.path.join(root, "bench"))
              for f in fs}
    metric = os.path.join(root, "bench", "metrics", "tiny.count.py")
    with open(metric, "w") as f:
        f.write('LAYER, UNIT, SOURCE, MOVES = "load generator", "count", '
                '"host_clock", "output_tok_s"\n\n\ndef read(ctx):\n'
                '    return len(ctx.recs)\n')
    bm = spec.benchmark(root)
    bm["per_layer"].append({"name": "tiny.count", "unit": "count",
                            "better": "higher", "source": "host_clock",
                            "layer": "load generator", "moves":
                            "output_tok_s", "workloads": [testing.TINY]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    from bench import run
    c = run.load_cell(testing.TINY, root)
    assert c.mix["max_batch"] == 4 and c.conf["num_hidden_layers"] == 2
    names = [m["name"] for m in spec.metrics_for(bm, testing.TINY, True)]
    assert "tiny.count" in names
    assert spec.reader("tiny.count", root).read(
        type("Ctx", (), {"recs": [1, 2]})) == 2
    # the files the benchmark already had are unchanged
    for rel in before:
        with open(os.path.join(root, rel), "rb") as a:
            src = os.path.join(ROOT, rel)
            if os.path.exists(src):
                with open(src, "rb") as b:
                    assert a.read() == b.read(), rel
