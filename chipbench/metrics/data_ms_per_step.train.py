"""Mean host milliseconds per window step inside the data source's
``batch_at``, which the loop calls on its critical path."""


def read(record: dict):
    data = record.get("data_s")
    return 1e3 * sum(data) / len(data) if data else None
