"""How evenly the scheduler's device pool shared the front-end tiles
over the traced stretch: the tiles launched on its least-used device
times the number of pool devices, over all the tiles launched, in per
cent (100 is even; 0 means a device launched nothing). Read from the
per-device counters encode.batched_tiles.d<N>, which every launch
reports for every pool device (an unused one as 0), so their number is
the pool's size; a program without them gives nothing."""

PREFIX = "encode.batched_tiles.d"


def read(ctx):
    tiles = [v for k, v in ctx["counters"].items() if k.startswith(PREFIX)]
    if not tiles or not sum(tiles):
        return None
    return 100.0 * min(tiles) * len(tiles) / sum(tiles)
