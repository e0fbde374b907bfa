"""Engine step: host time per window step from the program's own spans —
``orca.step`` less ``orca.wait``, the time the host spends on the step
besides waiting for the device (ms).  Nothing without the program's step
records."""
from bench import scopes as S


def read(ctx):
    recs = S.window_records(ctx)
    if not recs:
        return None
    return 1e3 * sum(r.seconds - r.span_seconds("orca.wait")
                     for r in recs) / len(recs)
