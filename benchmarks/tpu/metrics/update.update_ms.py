"""Device time of the jitted update program (noise and optimizer) per
logical step, from the trace."""


def read(red, counters, cell):
    prog = red["programs"].get("update")
    if not prog or not prog["n"]:
        return None
    return 1e3 * prog["s"] / prog["n"]
