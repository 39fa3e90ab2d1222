"""A throwaway cell at a size the CPU runs in seconds, written as new files
under a temporary root, the way a later change adds a cell: a
configuration, a traffic mix, a limits file, a reference and a per-layer
metric reader, plus a BENCHMARK.json entry naming them."""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "source": "https://huggingface.co/Qwen/Qwen3-1.7B/blob/main/config.json",
    "reference": "qwen3", "attention_bias": False, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "initializer_range": 0.02, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": True, "torch_dtype": "float32", "vocab_size": 512,
    "run": {"arch": "qwen3-1.7b", "attention": "fastmax2", "chunk_size": 16,
            "max_slots": 3, "max_len": 256}}

# wide enough that the layers, not the embedding, decide the greedy token:
# there a lower precision flips some served tokens
WIDE = dict(TINY, hidden_size=256, head_dim=64, intermediate_size=512,
            vocab_size=2048, torch_dtype="bfloat16")

MIXES = {
    "tiny_closed": {"kind": "serve", "loop": "closed", "clients": 5,
                    "requests": 48,
                    "prompt": {"median": 24, "sigma": 0.6, "min": 8,
                               "max": 64},
                    "output": {"median": 8, "sigma": 0.5, "min": 4,
                               "max": 16},
                    "lead_s": 0.3, "check": {"tokens": 48,
                                             "max_requests": 4}},
    "wide_closed": {"kind": "serve", "loop": "closed", "clients": 5,
                    "requests": 48,
                    "prompt": {"median": 24, "sigma": 0.6, "min": 8,
                               "max": 64},
                    "output": {"median": 16, "sigma": 0.5, "min": 8,
                               "max": 32},
                    "lead_s": 0.3, "check": {"tokens": 200,
                                             "max_requests": 12}},
    "tiny_train": {"kind": "train", "seq": 64, "batch": 2, "batches": 4,
                   "zipf_a": 1.2, "check_steps": 3,
                   "optimizer": {"lr": 3e-4, "total_steps": 100,
                                 "warmup_steps": 10, "b1": 0.9, "b2": 0.95,
                                 "eps": 1e-8, "weight_decay": 0.1,
                                 "clip_norm": 1.0}},
}

LIMITS = {"serve": {"logit_gap_max": 1e-3},
          "train": {"loss_gap": 1e-4, "gnorm_gap": 1e-3,
                    "grad_norm_gap": 1e-3, "update_norm_gap": 1e-3}}

READER = '''"""A metric only this throwaway cell has."""


def read(r):
    return 42.0 if r.trace.device else None
'''


def make_root(tmp, mix_name: str) -> tuple:
    """Writes the cell's files under `tmp`; returns (spec, workload)."""
    mix = MIXES[mix_name]
    cell = f"tiny-{mix_name}"
    b = os.path.join(tmp, "bench")
    for d in ("configs", "traffic", "cells", "metrics", "refs"):
        os.makedirs(os.path.join(b, d), exist_ok=True)
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump(TINY, f)
    with open(os.path.join(b, "traffic", f"{mix_name}.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(b, "cells", f"{cell}.json"), "w") as f:
        json.dump({"limits": LIMITS[mix["kind"]]}, f)
    with open(os.path.join(b, "metrics", "tiny_metric.py"), "w") as f:
        f.write(READER)
    shutil.copy(os.path.join(ROOT, "bench", "refs", "qwen3.py"),
                os.path.join(b, "refs", "qwen3.py"))
    shutil.copy(os.path.join(ROOT, "bench", "metrics", "idle_share.decode.py"),
                os.path.join(b, "metrics", "idle_share.decode.py"))
    moves = "train_tok_s" if mix["kind"] == "train" else "serve_tok_s"
    spec = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json"}],
        "workloads": [{"name": cell, "config": "tiny", "traffic": mix_name,
                       "chips": 1}],
        "end_to_end": [
            {"name": "serve_tok_s", "unit": "tokens/s",
             "workloads": ["tiny-tiny_closed"]},
            {"name": "itl_p95_ms", "unit": "ms",
             "workloads": ["tiny-tiny_closed"]},
            {"name": "train_tok_s", "unit": "tokens/s",
             "workloads": ["tiny-tiny_train"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "tiny_metric", "unit": "%", "moves": moves,
             "workloads": [cell]},
            {"name": "idle_share.decode", "unit": "%", "moves": moves}],
    }
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return spec, spec["workloads"][0]


def run_tiny(tmp, mix_name, *, seed=7, seconds=1.0, trace=0, control=None):
    """Drive the whole run of the throwaway cell on the CPU (no device
    guard; the kernel-route checks, which need a TPU, pass through)."""
    import time

    from bench import device, run

    spec, workload = make_root(tmp, mix_name)
    cell = run.Cell(spec, workload, seed, seconds, trace,
                    t_start=time.perf_counter(), root=str(tmp))
    cell.routes = _NoRoutes()
    cell.clock = device.CompileClock()
    cell.control = control
    label = {"platform": "cpu", "kind": "cpu", "count": 1}
    orig, orig_peaks = device.require_kernels, device.peaks
    try:
        device.require_kernels = lambda *a, **k: None
        # nominal numbers so the readers run; a CPU run reports no device
        # metric
        device.peaks = lambda kind: {"bf16_flops": 1e12,
                                     "hbm_bytes_per_s": 1e11}
        for m in ("serve", "train"):
            __import__(f"bench.{m}")
            sys.modules[f"bench.{m}"].require_kernels = device.require_kernels
        return run.run_cell(cell, label)
    finally:
        device.require_kernels, device.peaks = orig, orig_peaks
        for m in ("serve", "train"):
            sys.modules[f"bench.{m}"].require_kernels = orig


class _NoRoutes:
    lines: list = []

    def check(self, expect=()):
        return None


def lengths(reqs):
    return sorted(len(r.prompt) for r in reqs), \
        sorted(r.max_new for r in reqs)



def load_ref():
    """The reference module, loaded by path as the harness loads it."""
    from bench import run
    return run.load_module(os.path.join(ROOT, "bench", "refs", "qwen3.py"),
                           "bench_ref_qwen3")
