"""A tiny cell for the CPU tests of the benchmark: the dense family at a
size a test run holds, with a real cell's limits."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

DENSE = {"name": "tiny-dense", "family": "dense", "num_layers": 3,
         "d_model": 64, "num_heads": 4, "num_kv_heads": 2, "d_ff": 128,
         "vocab_size": 256, "head_dim": 0, "rope_theta": 10000.0,
         "norm_eps": 1e-5, "tie_embeddings": True,
         "vertical": {"num_clients": 2, "tower_layers": 1, "merge": "avg"}}


def tiny_cell(arch=DENSE, limits_of="smollm-360m.train-b4s256-w1"):
    mix = json.loads((BENCH / "traffic" / "train-b4s256-w1.json").read_text())
    mix["seq"] = 16
    limits = json.loads((BENCH / "limits" / f"{limits_of}.json").read_text())
    return {"name": "tiny", "chips": 1, "arch": arch, "mix": mix,
            "limits": limits["limits"], "per_layer": [],
            "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"}]}
