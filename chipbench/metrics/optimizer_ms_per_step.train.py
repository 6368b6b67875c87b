"""Device self milliseconds per traced step under the program's
``optimizer`` scope (clipping and the AdamW update), averaged over the
devices; 0 where the traced step names other scopes but not this one."""


def read(record: dict):
    scopes = (record.get("trace") or {}).get("scope_ms_per_step")
    return scopes.get("optimizer", 0.0) if scopes else None
