"""Tiny cells for the benchmark's CPU tests, of each family's ``TINY``.

The benchmark lives at the repository's root (``chipbench/``); put the
root on the path so the tests import it as the command does.
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def tiny_cell(family: str, limits_of: str = "olmo-1b-8l.pretrain-2k",
              seq_len: int = 64, batch: int = 2):
    """A CPU-sized training cell of ``family`` (its module's ``TINY``),
    held to the limits of the benchmark cell ``limits_of``."""
    from chipbench import reference
    from chipbench.spec import Cell, load_benchmark
    bench = load_benchmark()
    traffic = json.loads((ROOT / "chipbench" / "traffic" /
                          "pretrain-2k.json").read_text())
    traffic.update(seq_len=seq_len, global_batch=batch)
    limits = json.loads((ROOT / "chipbench" / "limits" /
                         f"{limits_of}.json").read_text())["limits"]
    return Cell(name=f"{family}-tiny", chips=1,
                config=dict(reference.family(family).TINY),
                traffic=traffic, limits=limits,
                end_to_end=list(bench["end_to_end"]),
                per_layer=[m for m in bench["per_layer"]
                           if "workloads" not in m])
