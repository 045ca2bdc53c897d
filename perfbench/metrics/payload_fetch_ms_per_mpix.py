"""Milliseconds per encoded megapixel of fetching the packed Tier-1
payload from the device: the /metrics stage encode.fetch_payload over
its own megapixels (each source pixel once, on its first attempt)."""
from metrics._common import stage


def read(ctx):
    entry = stage(ctx, "encode.fetch_payload")
    if not entry or not entry.get("mpixels"):
        return None
    return 1e3 * entry["sum_s"] / entry["mpixels"]
