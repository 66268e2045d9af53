"""A checkout in a temporary directory with one tiny cell, for running
the harness on the CPU.

The tiny cell keeps the served path of the real cells (router, BM25,
gateway, continuous engine, paged cache, Pallas paged decode in
interpret mode) at the program's smoke widths, over a small corpus.
Tests run the harness in-process with ``require_chip=False``; nothing
they print is a device measurement.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

TINY = "tiny.rag"
TINY_CLOSED = "tiny.longform"


def tiny_config() -> dict:
    return {
        "source": "smoke preset of the program (CPU tests only)",
        "program_config": "qwen1.5-32b", "program_variant": "smoke",
        "model_type": "qwen2",
        "hidden_size": 256, "intermediate_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0, "tie_word_embeddings": True,
        "torch_dtype": "bfloat16",
        "serving": {"mp": 1, "page_size": 16, "prefill_batch": 2,
                    "max_prompt_len": 56, "sync_every": 4, "paged": True,
                    "prefix_sharing": False, "flash_decode": True},
        "router": {"seed": 0, "n_train": 60, "n_eval": 10,
                   "n_paragraphs": 40, "n_epochs": 1},
    }


def tiny_mix(real_mix: dict) -> dict:
    mix = json.loads(json.dumps(real_mix))
    mix["corpus"].update(n_paragraphs=60, n_questions=80)
    mix.update(num_slots=4, max_new_tokens=8, check_requests=3,
               trace_lead_s=0.5, trace_s=1.0)
    mix["arrivals"] = {"process": "poisson", "rate": 4.0, "gap_seed": 1}
    return mix


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    """A copy of the benchmark with the tiny cell added by files and
    entries only."""
    root = tmp_path / "co"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", root / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    real = json.loads((BENCH / "mixes" / "rag_steady.json").read_text())
    (root / "bench/configs/tiny.json").write_text(
        json.dumps(tiny_config()))
    (root / "bench/mixes/tiny_rag.json").write_text(
        json.dumps(tiny_mix(real)))
    closed = tiny_mix(real)
    del closed["arrivals"]
    closed.update(loop="closed", concurrency=4, ramp_s=1.0,
                  max_new_tokens=24, check_requests=2)
    (root / "bench/mixes/tiny_longform.json").write_text(json.dumps(closed))
    # the committed cell's logit_gap limit (tiny sound runs read
    # 0-0.042 on the CPU), over the tiny cells' fewer served tokens
    committed = json.loads((BENCH / "limits" /
                            "qwen1.5-32b.longform.json").read_text())
    for cell in (TINY, TINY_CLOSED):
        (root / f"bench/limits/{cell}.json").write_text(json.dumps(
            {"logit_gap": committed["logit_gap"], "checked_tokens": 16}))
    spec["configs"].append({"name": "tiny", "source": "smoke preset",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"] += [
        {"name": TINY, "config": "tiny", "traffic": "tiny_rag",
         "chips": 1, "why": "CPU tests"},
        {"name": TINY_CLOSED, "config": "tiny", "traffic": "tiny_longform",
         "chips": 1, "why": "CPU tests"}]
    # each tiny cell reports the metrics of the real cell of its loop
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "qwen1.5-32b.longform" in m.get("workloads", ()):
            m["workloads"].append(TINY_CLOSED)
        if "qwen1.5-32b.rag_steady" in m.get("workloads", ()):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    return root
