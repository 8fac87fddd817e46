"""Tiny configurations and cells for the benchmark's CPU tests."""
from __future__ import annotations

import copy

from bench import common


def config(qk_norm: bool, layers: int = 3) -> dict:
    return {"name": "tiny", "program_arch": "qwen3-4b",
            "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": layers, "vocab_size": 256,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "qk_norm": qk_norm,
            "tie_word_embeddings": False, "torch_dtype": "bfloat16"}


def train_ctx(seed: int = 2 ** 31 + 11, seconds: float = 0.5) -> dict:
    cell = "qwen3-4b-35L.ezo.b4s1024"
    tr = copy.deepcopy(common.load("traffic", "ezo.b4s1024"))
    tr.update(batch=2, seq=16)
    return {"cell": cell, "seed": seed, "seconds": seconds, "trace": False,
            "workload": common.load("workloads", cell),
            "config": config(True), "traffic": tr,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def serve_ctx(seed: int = 2 ** 31 + 5, seconds: float = 2.0) -> dict:
    cell = "phi4-mini-3.8b.serve.chat-poisson"
    tr = copy.deepcopy(common.load("traffic", "serve.chat-poisson"))
    tr["rate_per_s"] = 4.0
    tr["prompt"].update(median=12, min=4, max=24)
    tr["output"].update(median=6, min=2, max=10)
    tr["serve"].update(page_size=4, max_batch_slots=4, max_seq_len=40)
    tr["check"] = {"sampled": 4}
    workload = dict(common.load("workloads", cell),
                    limits={"sampled_logit_gap": 0.25})
    return {"cell": cell, "seed": seed, "seconds": seconds, "trace": False,
            "workload": workload,
            "config": config(False, layers=2), "traffic": tr,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
