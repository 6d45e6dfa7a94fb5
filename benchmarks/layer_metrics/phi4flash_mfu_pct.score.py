"""Model step: the whole step's share of the chip's bf16 peak. Real tokens
per second of the traced window (host clock, every call and gap counted)
times the FLOPs one real token of the cell's rows needs (`work/phi4flash.py`:
the products and the head, and the differential cores at the keys each row's
own length gives its queries; 9.63 GFLOP over the cell's four rows), over the
peak. It bounds every kernel's claim in this cell; padded positions earn
nothing, nor does the scan's arithmetic on the vector unit."""

from benchmarks.harness import spec, token_rows


def lengths_of(ctx):
    """The multiset of real lengths of a call's rows (`harness/token_rows.py`)."""
    traffic, config = ctx["traffic"], ctx["config"]
    rows = int(config["assumed"]["batch_size"]) * int(traffic["batches_per_call"])
    return [int(n) for n in token_rows.lengths_multiset(
        traffic["lengths"], rows, int(traffic["cap"]))]


def read(ctx):
    if not ctx["work"] or not ctx["window_s"]:
        return None
    work = spec.bench_module("work", "phi4flash")
    flops_per_s = ctx["work"] / ctx["window_s"] \
        * work.flops_per_token(ctx["config"], lengths_of(ctx))
    peak = ctx["peaks"].peaks_for(ctx["device_kind"])["flops_per_s"]
    return 100.0 * flops_per_s / peak
