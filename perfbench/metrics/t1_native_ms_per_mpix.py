"""Native Tier-1 coder milliseconds per encoded megapixel: the /metrics
stage t1.native (the native call alone, summed over the Tier-1 threads)
over the megapixels of encode.host_code."""
from metrics._common import stage


def read(ctx):
    native = stage(ctx, "t1.native")
    host = stage(ctx, "encode.host_code")
    if not native or not host or not host.get("mpixels"):
        return None
    return 1e3 * native["sum_s"] / host["mpixels"]
