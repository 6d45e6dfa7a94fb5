"""Multi-token prediction: device self time of the MTP module, a batch: every
event under the scope `mtp` (`CausalLM`: `fuse`, the embedding of the next
ids, the two norms and `W_eh`; `layer<L>`, its latent-attention and expert
layer; `logits`, its norm and the head's loop over blocks of positions),
over the program's runs in the traced calls (`harness/scopes.py`). A
program without the module, or a call that does not fetch `mtp_logprob`,
has no such scope: nothing to read."""

from benchmarks.harness import scopes

PART = r"(^|/)mtp(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART, a_layer=False)
