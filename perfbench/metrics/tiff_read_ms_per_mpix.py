"""Milliseconds per source megapixel of reading the TIFF master: the
/metrics stage convert.tiff_read over its own megapixels."""
from metrics._common import stage


def read(ctx):
    entry = stage(ctx, "convert.tiff_read")
    if not entry or not entry.get("mpixels"):
        return None
    return 1e3 * entry["sum_s"] / entry["mpixels"]
