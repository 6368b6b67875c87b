"""The control, the reference computed with float8 matmul operands (the
step below the configurations' bfloat16), comes out not correct against
each cell's limits; the reference against itself comes out correct.

At a CPU size: two layers at a quarter of the published width, 4 rows
of 128 tokens, the cell's own optimizer.  On the chip the same
comparison ran at each cell's own size (PERF.md, section 2)."""

import json
from pathlib import Path

import jax
import pytest

from chipbench import check, data, reference
from chipbench.reference.common import key_from_seed

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"family": "dense", "num_hidden_layers": 2, "hidden_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 128,
         "intermediate_size": 2048, "vocab_size": 4096,
         "embedding_rows": 4096, "rope_theta": 10000.0,
         "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
         "compute_dtype": "bfloat16"}
CELLS = ["olmo-1b-8l.pretrain-2k"]


def _limits(cell):
    return json.loads((ROOT / "chipbench" / "limits" /
                       f"{cell}.json").read_text())["limits"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_reference_passes(cell):
    c = SMALL
    opt = json.loads((ROOT / "chipbench" / "traffic" /
                      "pretrain-2k.json").read_text())["optimizer"]
    seed = 2**31 + 23
    rows = [data.synthetic_lm(seed, s, c["vocab_size"], 128, 4)
            for s in range(3)]
    key = key_from_seed(seed)
    devices = jax.devices()[:1]
    ref = reference.run(c, opt, key, rows, devices)
    again = reference.run(c, opt, key, rows, devices)
    ctl = reference.run(c, opt, key, rows, devices, control=True)
    limits = _limits(cell)
    assert check.judge(check.numbers(again, ref, rows, rows), limits)[0]
    ok, table = check.judge(check.numbers(ctl, ref, rows, rows), limits)
    assert not ok, table
