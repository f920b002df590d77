"""Mean synced prefill time per prompt of the requests served before the
profiled slice, in ms, from the engine's per-request records."""
from records import head_stats


def read(ctx):
    recs = head_stats(ctx, "prefill_s")
    if not recs:
        return None
    return 1e3 * sum(r.prefill_s for r in recs) / len(recs)
