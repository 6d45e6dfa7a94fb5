"""Kernels: the language model's head (`head_logprob`, the Pallas kernel of
`models/transformer.py`: every position's product with the whole head in
tiles, the online logsumexp, the target's logit; not the final norm before
it) against its roofline: least time of the head passes' work for a batch's
real tokens, 2 x hidden x vocab FLOP a real token a pass and the head's
bfloat16 weights read once a pass (`hidden_size`, `vocab_size` of the cell's
configuration), over the kernel's device seconds a batch. A pass is one
event of the kernel a run of the program: two where the call fetches
`mtp_logprob`, whose module goes through the same head. Pads cost the kernel
time and earn nothing, so it cannot pass 100%. A program whose head does not
run the kernel has no such event: nothing to read."""

KERNEL_PATTERN = r"^%?head_logprob\b"
MODULE_PATTERN = r"^jit_fused\("


def read(ctx):
    seconds, events = ctx["trace"].op_seconds(KERNEL_PATTERN)    # raises if none
    _, runs = ctx["trace"].module_seconds(MODULE_PATTERN)
    tokens = ctx["counters"].get("real_tokens")
    if not tokens:
        return None
    hidden, vocab = int(ctx["config"]["hidden_size"]), int(ctx["config"]["vocab_size"])
    passes = events / runs
    flops = passes * 2.0 * hidden * vocab * tokens / runs
    moved = passes * 2.0 * hidden * vocab
    least, _bound = ctx["peaks"].least_seconds(flops, moved, ctx["device_kind"])
    return 100.0 * least / (seconds / runs)
