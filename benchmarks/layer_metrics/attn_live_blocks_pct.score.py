"""Attention kernels: the query blocks of 512 that hold a position up to
their row's last real token, over all query blocks of 512, in the calls'
batches (`BatchTiming.counts`: `attn_live_blocks` over `attn_query_blocks`,
counted on the host from the shipped ids by the function that bounds the
kernels, `transformer.live_lengths`). The kernels compute only those blocks;
the rest fetch and compute nothing. A program that counts nothing (one whose
kernels compute every block) leaves the metric out."""


def read(ctx):
    live = total = 0
    for record in ctx["counters"].get("ingest_records") or ():
        counts = getattr(record, "counts", None) or {}
        live += int(counts.get("attn_live_blocks", 0))
        total += int(counts.get("attn_query_blocks", 0))
    if not total:
        return None
    return 100.0 * live / total
