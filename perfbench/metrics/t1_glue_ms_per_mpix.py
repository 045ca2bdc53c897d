"""Milliseconds per encoded megapixel of host Tier-1 outside the native
coder (marshalling its results, distortion correction): the /metrics
stage encode.host_code less t1.native, over encode.host_code's
megapixels."""
from metrics._common import stage


def read(ctx):
    native = stage(ctx, "t1.native")
    host = stage(ctx, "encode.host_code")
    if not native or not host or not host.get("mpixels"):
        return None
    return 1e3 * (host["sum_s"] - native["sum_s"]) / host["mpixels"]
