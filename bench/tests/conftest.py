"""Test set-up: the benchmark's own modules and the program on the path,
JAX on the CPU, and a checkout in a temporary directory that holds the
benchmark plus smoke-width cells added as data files only."""

from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "model_type": "qwen3", "tie_word_embeddings": False,
    "bench": {
        "arch": "dense_decoder", "qk_norm": True,
        "program": {"arch": "qwen3-14b-smoke", "set": {"n_kv_heads": 2}},
        "serving": {"slots": 4, "max_seq": 128, "max_prompt": 128,
                    "page_size": 16, "chunk": 4, "n_pages": None},
    },
}

SMOKE_TRAFFIC = {
    "chat-smoke": {
        "kind": "sessions", "why": "smoke",
        "system_prompts": {"count": 2, "tokens": 32,
                           "popularity_zipf_s": 1.0},
        "turns": {"min": 1, "max": 3},
        "user_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 24},
        "answer_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
        "think_s": {"mean": 0.05}, "lead_in_s": 0.3, "drain_cap_s": 5.0},
    "batch-smoke": {
        "kind": "backlog", "why": "smoke", "depth": 8,
        "prompt_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 24},
        "answer_tokens": {"median": 16, "sigma": 0.5, "min": 4, "max": 48},
        "lead_in_s": 0.3},
}

SMOKE_CELL = {
    "rate_per_s": 8.0,
    # at these widths on the CPU the program reads at most 0.014 and the
    # fp8 control at least 0.041 over five seeds in each cell
    "correct": {"max_logit_gap": 0.025, "sample": 4,
                "min_checked_tokens": 10},
}


def add_smoke_cells(root: Path) -> list[str]:
    """Add smoke-width cells to the checkout at `root` by adding files and
    entries only."""
    bench = root / "bench"
    (bench / "configs" / "smoke-d2.json").write_text(
        json.dumps(SMOKE_CONFIG))
    for name, params in SMOKE_TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(params))
    cells = [f"smoke-d2.{t}" for t in SMOKE_TRAFFIC]
    for c in cells:
        (bench / "cells" / f"{c}.json").write_text(json.dumps(SMOKE_CELL))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "smoke-d2", "source": "test",
                            "file": "bench/configs/smoke-d2.json",
                            "reduced": [], "why": "smoke"})
    for c, t in zip(cells, SMOKE_TRAFFIC):
        spec["workloads"].append({"name": c, "config": "smoke-d2",
                                  "traffic": t, "chips": 1, "why": "smoke"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "chat" if any("chat" in w for w in m["workloads"]) \
                else "batch"
            m["workloads"] += [c for c in cells if kind in c]
    # a per-layer metric added as a reader file and an entry
    (bench / "metrics" / "served_tokens.smoke.py").write_text(
        "def read(run):\n    return float(run.window.tokens_in_window)\n")
    spec["per_layer"].append({
        "name": "served_tokens.smoke", "unit": "tokens", "better": "higher",
        "source": "host_clock", "layer": "scheduler",
        "moves": "ttft_mean_ms", "workloads": [cells[0]]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cells


@pytest.fixture(scope="session")
def smoke_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    add_smoke_cells(root)
    return root
