"""Idle device time per logical step while the host is in the program's
input path: the idle seconds during which ``fit/sample``, ``fit/fetch`` or
``fit/place`` was open on the host (at any depth, so a dataset's own spans
inside them count), from the trace."""

from phase_split import INPUT_SPANS


def read(red, counters, cell):
    under = red.get("idle_under", {})
    hit = [under[s] for s in INPUT_SPANS if s in under]
    if not hit or not counters["steps"]:
        return None
    return 1e3 * sum(hit) / counters["steps"]
