"""Tiny cells for the benchmark's CPU tests.

The benchmark lives at the repository's root (``chipbench/``); put the
root on the path so the tests import it as the command does.
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY = {
    "dense": {
        "name": "olmo-tiny", "arch": "olmo-1b", "smoke": True,
        "family": "dense", "num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "intermediate_size": 256, "vocab_size": 512, "embedding_rows": 512,
        "rope_theta": 10000.0, "layer_norm_eps": 1e-5,
        "tie_word_embeddings": True, "compute_dtype": "float32",
        "param_dtype": "float32"},
    "rwkv6": {
        "name": "rwkv6-tiny", "arch": "rwkv6-1.6b", "smoke": True,
        "family": "rwkv6", "num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "intermediate_size": 128, "vocab_size": 512, "embedding_rows": 512,
        "tie_word_embeddings": False, "compute_dtype": "float32",
        "param_dtype": "float32"},
}


def tiny_cell(family: str, limits_of: str = "olmo-1b-8l.pretrain-2k",
              seq_len: int = 64, batch: int = 2):
    """A CPU-sized training cell of ``family``, held to the limits of the
    benchmark cell ``limits_of``."""
    from chipbench.spec import Cell, load_benchmark
    bench = load_benchmark()
    traffic = json.loads((ROOT / "chipbench" / "traffic" /
                          "pretrain-2k.json").read_text())
    traffic.update(seq_len=seq_len, global_batch=batch)
    limits = json.loads((ROOT / "chipbench" / "limits" /
                         f"{limits_of}.json").read_text())["limits"]
    return Cell(name=f"{family}-tiny", chips=1, config=dict(TINY[family]),
                traffic=traffic, limits=limits,
                end_to_end=list(bench["end_to_end"]),
                per_layer=[m for m in bench["per_layer"]
                           if "workloads" not in m])
