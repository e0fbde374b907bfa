"""Engine step: blocking device-to-host reads per window step, counted by
the program where it reads.  Nothing without the program's step
records."""
from bench import scopes as S


def read(ctx):
    recs = S.window_records(ctx)
    if not recs:
        return None
    return sum(r.counts.get("reads", 0) for r in recs) / len(recs)
