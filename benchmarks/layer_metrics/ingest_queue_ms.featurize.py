"""Ingest: mean time the device side waited for the host to fill a batch
(`BatchTiming.queue_s` of `parallel/ingest.py`), over the traced window's batches."""


def read(ctx):
    records = ctx["counters"].get("ingest_records") or []
    if not records:
        return None
    return 1e3 * sum(r.queue_s for r in records) / len(records)
