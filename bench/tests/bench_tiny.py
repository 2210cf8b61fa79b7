"""A benchmark root in a temporary directory with one tiny cell, for
running the harness on the CPU: the real pieces under ``bench/`` and
whatever configuration, mix and metrics a test adds as files."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_DENSE = {
    "source": "https://huggingface.co/microsoft/Phi-4-mini-instruct",
    "family": "dense", "model_type": "phi3",
    "hidden_size": 96, "intermediate_size": 256, "num_attention_heads": 6,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "initializer_range": 0.02,
    "engine": {"n_slots": 4, "max_seq": 256, "n_pages": 33, "page_size": 16,
               "prefill_chunk": 32},
    "check": {"logit_gap_max": 0.05, "min_tokens": 40},
}
TINY_CHAT = {
    "kind": "open_loop", "arrivals": {"rate_per_s": 6.0},
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6, "min": 4, "max": 120},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 2, "max": 24},
}


def make_root(tmp: Path, *, config=None, traffic=None, cell="tiny-chat",
              per_layer=(), metric_files=None) -> Path:
    """A checkout-like root: ``BENCHMARK.json`` with one cell, its
    configuration and mix as files, and the real metric readers and
    roofline counts copied beside any ``metric_files`` ({name: source})."""
    tmp = Path(tmp)
    for sub in ("metrics", "roofline"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub,
                        dirs_exist_ok=True)
    (tmp / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "traffic").mkdir(parents=True, exist_ok=True)
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(config or TINY_DENSE))
    (tmp / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps(traffic or TINY_CHAT))
    for name, src in (metric_files or {}).items():
        (tmp / "bench" / "metrics" / f"{name}.py").write_text(src)
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 3,
        "configs": [{"name": "tiny", "source": TINY_DENSE["source"],
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "tiny"}],
        "workloads": [{"name": cell, "config": "tiny", "traffic": "tiny",
                       "chips": 1, "why": "tiny"}],
        "end_to_end": [
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "output_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.25, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [dict(name=n, unit=u, better="higher",
                           source="program_counter", layer="x",
                           moves="ttft_p95_ms") for n, u in per_layer],
    }
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, *, seed=7, seconds=3.0, trace=False, fault=None,
        cell="tiny-chat", control=False) -> dict:
    import time

    from bench import harness

    return harness.run(cell, seed, seconds, trace, t_start=time.monotonic(),
                       root=root, require_chip=False, fault=fault,
                       control=control)
