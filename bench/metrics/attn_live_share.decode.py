"""Kernels: share of the paged decode kernel's compute blocks that ran —
the blocks up to each decoding row's last valid position, over the blocks
a walk of every block-table entry would launch, summed over the window's
steps (%).  Counted by the program from the positions it uploads.
Nothing without the program's ``attn_blocks`` counter."""
from bench import scopes as S


def read(ctx):
    recs = S.window_records(ctx)
    launched = sum(r.counts.get("attn_blocks", 0) for r in recs)
    if not launched:
        return None
    return 100.0 * sum(r.counts.get("attn_blocks_live", 0)
                       for r in recs) / launched
