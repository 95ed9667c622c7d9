"""Device time from gradient to accumulator per physical batch: the
operations under the program's named scope ``grad/accumulate`` (the clip
coefficients, the flat tile and its clip_accum fold, the cast and the add
into the flat accumulator), from the trace."""

from phase_split import FOLD_PHASE


def read(red, counters, cell):
    rec = red.get("phases", {}).get("accumulate", {}).get(FOLD_PHASE)
    prog = red["programs"].get("accumulate")
    if not rec or not prog or not prog["n"]:
        return None
    return 1e3 * rec["s"] / prog["n"]
