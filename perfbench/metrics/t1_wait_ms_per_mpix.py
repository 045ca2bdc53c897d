"""Milliseconds per encoded megapixel that Tier-1 chunks waited for a
worker of the shared host Tier-1 pool: the /metrics stage
encode.t1_wait (from a chunk's submission to the start of its host
Tier-1 span, summed over the chunks) over its own megapixels (each
source pixel once, on its first attempt)."""
from metrics._common import stage


def read(ctx):
    entry = stage(ctx, "encode.t1_wait")
    if not entry or not entry.get("mpixels"):
        return None
    return 1e3 * entry["sum_s"] / entry["mpixels"]
