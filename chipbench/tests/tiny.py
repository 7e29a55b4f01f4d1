"""Tiny copies of the benchmark's cells, for runs on the CPU."""
from __future__ import annotations

import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

DECODER = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2,
           "d_head": 32, "d_ff": 256, "vocab_size": 512,
           "rope_theta": 10000.0, "rotary_pct": 1.0, "norm_eps": 1e-06,
           "window": 0, "causal": True, "add_sinusoidal_pos": False,
           "ffn_type": "gelu", "norm_type": "layernorm",
           "tie_embeddings": True, "dtype": "bfloat16"}
ENCODER = dict(DECODER, n_kv_heads=4, rotary_pct=0.0, causal=False,
               add_sinusoidal_pos=True)

SERVE = {
    "sc2-3b.complete": {
        "arrivals": {"process": "poisson", "rate_per_s": 30.0},
        "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.6,
                       "min": 9, "max": 100},
        "max_new": {"dist": "uniform", "min": 4, "max": 20},
        "serve": {"slots": 4, "max_len": 160, "check_every": 4,
                  "mca": False},
        "check": {"requests": 3}},
    "sc2-3b.batch-gen": {
        "pool": 256,
        "prompt_len": {"dist": "uniform", "min": 9, "max": 60},
        "max_new": {"dist": "uniform", "min": 10, "max": 30},
        "serve": {"slots": 4, "max_len": 128, "check_every": 4,
                  "mca": False},
        "check": {"requests": 2}},
    "bert-base.train": {"batch": 4, "seq": 32},
}


def run(workload: str, seed: int = 1, seconds: float = 1.5, **kw):
    import run as bench
    model = ENCODER if workload.startswith("bert") else DECODER
    return bench.run(workload, seed, seconds, trace=kw.pop("trace", False),
                     require_chip=False, config_override=model,
                     mix_override=SERVE[workload], **kw)
