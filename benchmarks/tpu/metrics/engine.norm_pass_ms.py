"""Device time of ghost clipping's norm pass per physical batch: the
operations under the program's named scope ``grad/norm_pass`` (the
eps-tapped forward, the backward to the taps and the per-layer norm
contractions, ghost_norm kernel included), from the trace."""


def read(red, counters, cell):
    rec = red.get("phases", {}).get("accumulate", {}).get("grad/norm_pass")
    prog = red["programs"].get("accumulate")
    if not rec or not prog or not prog["n"]:
        return None
    return 1e3 * rec["s"] / prog["n"]
