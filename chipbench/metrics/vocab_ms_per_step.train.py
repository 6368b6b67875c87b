"""Device self milliseconds per traced step under the program's ``embed``
(the lookup) and ``head`` (final norm, unembedding, cross-entropy)
scopes, which do not nest, averaged over the devices; 0 where the traced
step names other scopes but neither of these."""


def read(record: dict):
    scopes = (record.get("trace") or {}).get("scope_ms_per_step")
    if not scopes:
        return None
    return scopes.get("embed", 0.0) + scopes.get("head", 0.0)
