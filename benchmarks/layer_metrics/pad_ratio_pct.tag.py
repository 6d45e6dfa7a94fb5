"""Token columns: real tokens of the traced calls over the positions the
program shipped to the chip for them (`BatchTiming.bytes_in` over the id's
size: rows padded to the cap, batches padded to their bucket). Every shipped
position is computed; only this share of them is work a caller asked for."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("padded_positions"):
        return None
    return 100.0 * c["real_tokens"] / c["padded_positions"]
