"""Device memory the window's programs hold on a chip while they run, in
GiB: the largest of the compiled accumulate's and update's arguments,
outputs not aliased to them and temporaries (``memory_analysis()``).  The
runtime's ``peak_bytes_in_use`` leaves the temporaries out, and they are
what decides whether a larger tile or physical batch fits."""


def read(red, counters, cell):
    if not counters["program_peak_bytes"]:
        return None
    return counters["program_peak_bytes"] / 2.0 ** 30
