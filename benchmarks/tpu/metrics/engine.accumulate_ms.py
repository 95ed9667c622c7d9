"""Device time of the jitted accumulate program per physical batch (the
clipping engine and the kernels inside it), from the trace."""


def read(red, counters, cell):
    prog = red["programs"].get("accumulate")
    if not prog or not prog["n"]:
        return None
    return 1e3 * prog["s"] / prog["n"]
