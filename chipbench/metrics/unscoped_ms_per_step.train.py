"""Device self milliseconds per traced step under none of the program's
five scopes, which do not nest (the layer scan's slicing and stacking,
the step's glue): all operations' self time less the time under the
five, averaged over the devices."""

FIVE = ("embed", "attention", "mlp", "head", "optimizer")


def read(record: dict):
    tr = record.get("trace") or {}
    scopes = tr.get("scope_ms_per_step")
    if not scopes:
        return None
    return tr["self_ms_per_step"] - sum(scopes.get(s, 0.0) for s in FIVE)
