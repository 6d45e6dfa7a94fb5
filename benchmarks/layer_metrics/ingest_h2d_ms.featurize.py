"""Ingest: mean host-to-device transfer time of a batch (`BatchTiming.h2d_s`,
measured on the ring's producer thread), over the traced window's batches."""


def read(ctx):
    records = ctx["counters"].get("ingest_records") or []
    if not records:
        return None
    return 1e3 * sum(r.h2d_s for r in records) / len(records)
