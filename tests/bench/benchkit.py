"""Helpers for the benchmark's CPU tests: a copy of the benchmark's
files with a small configuration and mix added, as a later change would
add them, and a run of that cell with the chip check skipped.

The small configuration is a dense decoder with GQA, RMSNorm and an
untied head (the Phi-4-mini path) at a width where the layers, not the
embedding, decide the next token, so that a step that keeps a stale
cache changes the served tokens.  The program has no such
configuration of its own; :func:`use_test_arch` lends it one.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TEST_ARCH = "bench-test-256"

TINY_CONFIG = {
    "name": "tiny-dense", "arch": TEST_ARCH,
    "source": "a small dense decoder for CPU tests",
    "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "vocab_size": 4096, "rope_theta": 10000.0, "partial_rotary_factor": 1.0,
    "tie_word_embeddings": False, "rms_norm_eps": 1e-06,
    # float32 serving: on the CPU the program then agrees with the
    # reference to rounding, whichever requests the check samples
    "serving": {"param_dtype": "float32", "compute_dtype": "float32",
                "max_len": 128, "resident_slots": 2},
    "correct": {"max_logit_gap": 0.001, "check_requests": 8,
                "check_tokens": 200},
}

TINY_MIX = {
    "why": "small mixed traffic for CPU tests",
    "lo": {"clients": "resident_slots", "deck": 4,
           "prompt": {"min": 8, "max": 32, "alpha": 1.0,
                      "buckets": [8, 16, 32]},
           "output": {"min": 8, "max": 64, "alpha": 1.0}},
    "hi": {"rate_per_s": 20.0, "deck": 8,
           "prompt": {"min": 4, "max": 16, "alpha": 1.0, "buckets": [8, 16]},
           "output": {"min": 2, "max": 4, "alpha": 1.0}},
}

TINY_CELL = "tiny-dense.tiny_mixed"


def use_test_arch(monkeypatch) -> None:
    """Let ``repro.configs.get_config`` name the test architecture."""
    import repro.configs
    from repro.configs.base import ArchConfig
    arch = ArchConfig(name=TEST_ARCH, family="dense", n_layers=2,
                      d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
                      vocab=4096, head_dim=64, norm="rmsnorm",
                      tie_embeddings=False)
    get = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config",
                        lambda name: arch if name == TEST_ARCH else get(name))


def tiny_root(tmp: Path, config: dict = TINY_CONFIG) -> Path:
    """A checkout of the benchmark's files with the small cell added by
    new files and new ``BENCHMARK.json`` entries only."""
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-dense.json").write_text(
        json.dumps(config))
    (root / "bench" / "traffic" / "tiny_mixed.json").write_text(
        json.dumps(TINY_MIX))
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": "bench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-dense",
                               "traffic": "tiny_mixed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def run_tiny(root: Path, seed: int = 7, seconds: float = 2.0,
             trace: bool = False):
    """One run of the small cell through ``bench.run.run_cell``."""
    from bench import run, spec
    cell = spec.cell(TINY_CELL, root)
    return run.run_cell(cell, seed, seconds, trace, dict(CPU_DEVICE),
                        CPU_PEAKS, root=root)
