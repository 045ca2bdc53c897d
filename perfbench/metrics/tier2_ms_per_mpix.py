"""Milliseconds per encoded megapixel of PCRD layer allocation, Tier-2
and codestream assembly: the /metrics stage encode.tier2 over its own
megapixels."""
from metrics._common import stage


def read(ctx):
    entry = stage(ctx, "encode.tier2")
    if not entry or not entry.get("mpixels"):
        return None
    return 1e3 * entry["sum_s"] / entry["mpixels"]
