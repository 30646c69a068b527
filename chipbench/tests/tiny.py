"""A CPU-sized cell: the qwen3 family at a few thousand parameters per
layer, driven through the harness exactly as a chip run is."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIPBENCH = HERE.parent
ROOT = CHIPBENCH.parent

TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            head_dim=16, d_ff=128, vocab_size=512)

MIX = {"rate_per_s": 25.0, "preroll_s": 0.5,
       "drain_s": 10, "size_seed": 5,
       "tenants": {"count": 2, "zipf_s": 1.0, "system_prompt_tokens": 32},
       "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
       "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 24}}


def program_config():
    from repro.configs.base import get_config

    return dataclasses.replace(get_config("qwen3-0.6b"), **TINY)


def config_dict(limit=0.05):
    cfg = json.loads((CHIPBENCH / "configs" / "qwen3-0.6b.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=512)
    cfg["engine"] = {"max_batch": 4, "num_blocks": 129, "block_size": 16}
    cfg["correctness"] = {"logit_gap_mean": limit}
    return cfg


def cell(monkeypatch, mix=None, limit=0.05):
    """A run_cell.Cell for the tiny model; patches the program's config
    registry so the engine builds it."""
    import repro.configs.base as base
    import run_cell

    pc = program_config()
    monkeypatch.setattr(base, "get_config", lambda name: pc)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = "qwen3-0.6b.chat-prefix"
    c = run_cell.Cell(name, bench=bench, cfg=config_dict(limit))
    c.mix = dict(mix or MIX)
    return c


def run(monkeypatch, seconds=1.5, trace=False, mix=None, limit=0.05, seed=3):
    import jax

    import run_cell

    c = cell(monkeypatch, mix, limit)
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}
    return run_cell.run_cell(c, seed, seconds, trace, jax.devices(), peaks,
                             0.0, say=lambda m: None)
