"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""


def read(red, counters, cell):
    if red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
