"""Padding rows over physical rows in the window (sampler + host input):
the rows the memory manager adds to fill the last physical batch of each
Poisson draw, which the device computes and the mask throws away."""


def read(red, counters, cell):
    rows = counters["physical_rows"]
    if not rows:
        return None
    return 100.0 * (rows - counters["examples"]) / rows
