"""Model step: device self time of the language model's head, a batch: every
event under the scope `head` (`CausalLM`: the streams' sum, the final norm,
the loop over blocks of positions: logits, the target's, the logsumexp), over
the program's runs in the traced calls (`harness/scopes.py`)."""

from benchmarks.harness import scopes

PART = r"(^|/)head(/|$)"


def read(ctx):
    return scopes.part_ms(ctx, PART, a_layer=False)
