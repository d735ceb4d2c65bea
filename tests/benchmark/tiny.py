"""A throw-away copy of the benchmark with tiny cells added to it, for the
CPU tests: the benchmark's files and `BENCHMARK.json` copied, the program's
package linked beside them, and one configuration, one traffic mix, one
per-layer metric and one cell of each runner added as NEW files plus entries,
with no file of the benchmark edited. That is also how a later PR adds them.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GPT_TINY = {
    "source": "tests only: the program's gpt_tiny preset",
    "model_type": "gpt2", "n_layer": 2, "n_embd": 64, "n_head": 4,
    "n_inner": None, "n_positions": 128, "vocab_size": 1024,
    "resid_pdrop": 0.1, "embd_pdrop": 0.1, "attn_pdrop": 0.1,
    "layer_norm_epsilon": 1e-05,
    "reference": "gpt2", "counts": "transformer",
    "kernels": {"decode_program": "jit_decode"},
    "serve": {"model": "gpt_tiny", "dtype": "float32", "max_slots": 4,
              "page_size": 8, "num_pages": 64, "max_pages_per_slot": 8,
              "prefill_buckets": [16, 32], "prefix_cache": True},
    "train": {"model": "gpt_tiny", "precision": "fp32",
              "attention_impl": "flash",
              "optimizer": {"name": "adamw", "learning_rate": 6e-4,
                            "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
                            "weight_decay": 0.1}},
}

RESNET_TINY = {
    "source": "tests only: the program's resnet26_thin preset",
    "architecture": "resnet26_thin", "block": "bottleneck",
    "stage_sizes": [2, 2, 2, 2], "width": 16, "num_classes": 10,
    "image_size": 64, "reference": "resnet", "counts": "cnn",
    "train": {"model": "resnet26_thin", "precision": "fp32",
              "optimizer": {"name": "sgd", "learning_rate": 0.001,
                            "momentum": 0.9, "weight_decay": 1e-4,
                            "label_smoothing": 0.1}},
}

# A BatchNorm network's gradient at a tiny batch is badly conditioned: two
# float32 computations of it part by percents (PERF.md), hence these limits.
TRAIN_IMG_TINY = {"runner": "train", "batch": 16, "image_size": 64,
                  "who": "tests only",
                  "limits": {"loss_gap": 2e-2, "grad_gap": 0.1,
                             "change_gap": 0.4}}

TRAIN_TINY = {"runner": "train", "batch": 4, "seq_len": 64,
              "who": "tests only",
              "limits": {"loss_gap": 1e-4, "grad_gap": 1e-2,
                         "change_gap": 1e-2}}

SERVE_TINY = {"runner": "serve", "rate_rps": 20.0, "warm_s": 0.5,
              "prompt": {"median": 12, "sigma": 0.5, "min": 4, "max": 30},
              "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
              "sample": 4, "who": "tests only",
              "limits": {"logit_gap": 1e-3}}

SERVE_E2E = [
    {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.03, "source": "host_clock"},
    {"name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock"},
    {"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock"},
]

# The benchmark has the serving readers' files and no serving cell yet, so
# their entries are added here, as a later PR would add them.
SERVE_PER_LAYER = [
    {"name": "decode_step_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "serve engine"},
    {"name": "decode_roofline", "unit": "%", "better": "higher",
     "source": "device_trace", "layer": "KV cache"},
    {"name": "step_mfu.serve", "unit": "%", "better": "higher",
     "source": "host_clock", "layer": "model step"},
    {"name": "device_idle_share.serve", "unit": "%", "better": "lower",
     "source": "device_trace", "layer": "device"},
]

TINY_METRIC = '''"""Tests only: number of `dispatch` spans the run recorded."""


def read(ctx):
    return float(len(ctx["spans"].durations("dispatch"))) or None
'''


def add(dst: str, rel: str, text: str) -> None:
    """Write a new file of the copy under `dst`; never over one that is
    there."""
    path = os.path.join(dst, rel)
    assert not os.path.exists(path), f"{rel} would edit an existing file"
    with open(path, "w") as fh:
        fh.write(text)


def make_checkout(dst: str) -> str:
    """Copy of the benchmark under `dst` with the tiny cells added."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "distributeddeeplearning_tpu"),
               os.path.join(dst, "distributeddeeplearning_tpu"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)

    add(dst, "benchmark/configs/gpt_tiny.json", json.dumps(GPT_TINY))
    add(dst, "benchmark/traffic/train_b4_s64.json", json.dumps(TRAIN_TINY))
    add(dst, "benchmark/metrics/dispatch_count.train.py", TINY_METRIC)
    add(dst, "benchmark/configs/resnet_tiny.json", json.dumps(RESNET_TINY))
    add(dst, "benchmark/traffic/train_b8_i32.json", json.dumps(TRAIN_IMG_TINY))
    spec["configs"].append({
        "name": "resnet_tiny", "source": RESNET_TINY["source"],
        "file": "benchmark/configs/resnet_tiny.json", "reduced": [],
        "why": "tests only"})
    spec["workloads"].append({
        "name": "resnet_tiny.train_b8_i32", "config": "resnet_tiny",
        "traffic": "train_b8_i32", "chips": 1, "why": "tests only"})
    spec["configs"].append({
        "name": "gpt_tiny", "source": GPT_TINY["source"],
        "file": "benchmark/configs/gpt_tiny.json", "reduced": [],
        "why": "tests only"})
    spec["workloads"].append({
        "name": "gpt_tiny.train_b4_s64", "config": "gpt_tiny",
        "traffic": "train_b4_s64", "chips": 1, "why": "tests only"})
    add(dst, "benchmark/traffic/serve_tiny.json", json.dumps(SERVE_TINY))
    spec["workloads"].append({
        "name": "gpt_tiny.serve_tiny", "config": "gpt_tiny",
        "traffic": "serve_tiny", "chips": 1, "why": "tests only"})
    have = {m["name"] for m in spec["end_to_end"]}
    for m in SERVE_E2E:  # where the benchmark has no serving cell yet
        if m["name"] not in have:
            spec["end_to_end"].append(dict(m, workloads=[]))
    for m in spec["end_to_end"]:
        if m["name"] in {e["name"] for e in SERVE_E2E}:
            m.setdefault("workloads", []).append("gpt_tiny.serve_tiny")
    have = {m["name"] for m in spec["per_layer"]}
    spec["per_layer"] += [dict(m, moves="itl_p95_ms") for m in SERVE_PER_LAYER
                          if m["name"] not in have]
    for m in spec["end_to_end"]:
        if m["name"] == "train_examples_per_s" and "workloads" in m:
            m["workloads"] += ["gpt_tiny.train_b4_s64",
                               "resnet_tiny.train_b8_i32"]
    spec["per_layer"].append({
        "name": "dispatch_count.train", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "loop",
        "moves": "train_examples_per_s",
        "workloads": ["gpt_tiny.train_b4_s64"]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    return dst


def check_cell_metrics(spec: dict, cell: str, metrics: set) -> None:
    """`cell` is one one-chip training cell of `spec`, and the per-layer
    metrics that list it are `metrics`, device times and roofline shares read
    from the trace (other cells may be on their lists beside it)."""
    mine = {m["name"]: m for m in spec["per_layer"]
            if cell in m.get("workloads", [])}
    assert set(mine) == metrics
    for m in mine.values():
        assert m["moves"] == "train_examples_per_s"
        assert m["source"] == "device_trace"
        assert m["unit"] == ("%" if "roofline" in m["name"] else "ms")
    entry = [w for w in spec["workloads"] if w["name"] == cell]
    assert len(entry) == 1 and entry[0]["chips"] == 1
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert cell in e2e["train_examples_per_s"]["workloads"]


def run_cell(checkout: str, *extra, timeout: int = 600):
    """Run the checkout's command; returns (returncode, stdout, stderr)."""
    with open(os.path.join(checkout, "BENCHMARK.json")) as fh:
        command = json.load(fh)["command"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable] + command[1:] + list(extra), cwd=checkout, env=env,
        capture_output=True, text=True, timeout=timeout)
    return proc.returncode, proc.stdout, proc.stderr
