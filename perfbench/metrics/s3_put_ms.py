"""Mean time of one upload to the store: the /metrics stage s3.put,
seconds (at full precision) over count, in milliseconds."""
from metrics._common import stage


def read(ctx):
    entry = stage(ctx, "s3.put")
    if not entry:
        return None
    return 1e3 * entry["sum_s"] / entry["count"]
