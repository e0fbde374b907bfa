"""The untied family with a fault in its reference: the logits read the
embedding, as a tied head would, instead of the head the program serves
with."""
from bench.families.llama import logits  # noqa: F401
from bench.tests.family_untied import (  # noqa: F401
    canonical, chunk_flops, decode_attention_work, hidden_states,
    program_tree, token_flops)
