"""ghost_norm's share of its roofline: the least time its calls in the window
could take on this chip (operations over peak FLOP/s or bytes over peak
bandwidth, whichever is larger, from counts/ghost_norm.py and peaks.json) over
the device time they took, in percent."""


def read(red, counters, cell):
    k = red["kernels"].get("ghost_norm")
    if not k or k["s"] <= 0:
        return None
    return 100.0 * k["least_s"] / k["s"]
