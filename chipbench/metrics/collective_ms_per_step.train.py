"""Device milliseconds per traced step spent in collective operations
(all-reduce, all-gather, reduce-scatter, all-to-all), averaged over the
devices; nothing to read on one device."""


def read(record: dict):
    tr = record.get("trace")
    if not tr or tr["devices"] < 2 or not tr["steps"]:
        return None
    return 1e3 * tr["collective_s"] / tr["steps"]
