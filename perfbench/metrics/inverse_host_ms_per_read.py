"""Host milliseconds of the device inverse per tile read served: the
/metrics stage decode.device_inverse (launch, transfer and wait, on the
host's clock) over the reads served in the traced window."""
from metrics._common import stage


def read(ctx):
    entry = stage(ctx, "decode.device_inverse")
    served = ctx["summary"].get("reads_served")
    if not entry or not served:
        return None
    return 1e3 * entry["sum_s"] / served
