"""A tiny benchmark tree for the CPU tests: a configuration, a training mix,
a BENCHMARK.json naming its cell, and limits, all written into a temporary
directory that is searched before the benchmark's own."""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[3]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip.registry import HERE, Registry  # noqa: E402

TINY = {"name": "tiny", "source": "test", "arch": "paper_150m",
        "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                      "n_kv_heads": 2, "d_ff": 128, "vocab": 512},
        "reduced": [], "num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "vocab_size": 512, "rms_norm_eps": 1e-05,
        "rope_theta": 10000.0, "tie_word_embeddings": False,
        "torch_dtype": "bfloat16", "param_dtype": "float32"}
# limits for these tiny sizes, from CPU runs of the sound program: each
# about 3x above what seeds 0-5 read
TRAIN_LIMITS = {"loss_gap": {"limit": 0.02}, "grad_gap": {"limit": 0.05},
                "delta_gap": {"limit": 0.05}, "sync_gap": {"limit": 0.05}}


def tiny_tree(tmp: Path) -> Registry:
    own = Registry()
    train = {**own.traffic("train.cocodc_paper"), "num_workers": 2,
             "num_fragments": 2, "local_steps": 20, "overlap_depth": 2,
             "local_batch": 2, "seq_len": 16, "warm_min_steps": 6,
             "warm_max_steps": 20}
    bench = own.benchmark()
    cells = {"tiny.train": ("tiny", "train.tiny")}
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                           "why": "test"} for n, (c, t) in cells.items()]
    bench["end_to_end"] = [
        {"name": "train_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["tiny.train"]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}]
    bench["per_layer"] = [
        {"name": "train_idle_share", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "trainer host loop",
         "moves": "train_tokens_per_s", "workloads": ["tiny.train"]}]
    files = {"configs/tiny.json": TINY, "traffic/train.tiny.json": train,
             "limits/tiny.train.json": TRAIN_LIMITS,
             "BENCHMARK.json": bench}
    for rel, obj in files.items():
        (tmp / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp / rel).write_text(json.dumps(obj))
    return Registry(roots=[tmp, HERE], benchmark=tmp / "BENCHMARK.json")
