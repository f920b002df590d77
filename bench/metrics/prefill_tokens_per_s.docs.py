"""Prompt tokens (unpadded) over the summed synced prefill time of the
requests served before the profiled slice, from the engine's per-request
records."""
from records import head_stats


def read(ctx):
    recs = head_stats(ctx, "prefill_s")
    seconds = sum(r.prefill_s for r in recs)
    if not recs or seconds <= 0.0:
        return None
    return sum(r.prompt_len for r in recs) / seconds
