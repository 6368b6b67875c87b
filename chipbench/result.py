"""The run's last line, and the numbers compared printed beside their
limits as the last lines of standard error."""

from __future__ import annotations

import json
import sys

KEYS = ("correct", "attempted", "failed", "metrics", "device")


def last_line(out: dict) -> str:
    """The result as one JSON line: the five result keys in order, then
    ``breakdown`` where traced, then ``checks`` last."""
    ordered = {k: out[k] for k in KEYS}
    if "breakdown" in out:
        ordered["breakdown"] = out["breakdown"]
    ordered["checks"] = out.get("checks", {})
    return json.dumps(ordered)


def emit(out: dict) -> None:
    line = last_line(out)
    for name, c in out.get("checks", {}).items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
