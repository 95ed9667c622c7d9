"""Device time of the accumulate's gradient phases per physical batch: the
operations under the program's named scopes ``grad/per_example``,
``grad/norm_pass``, ``grad/reweighted`` and ``grad/batch`` (the
per-example or batch forward and backward, ghost clipping's norm pass and
second backward), from the trace."""

from phase_split import GRAD_PHASES


def read(red, counters, cell):
    phases = red.get("phases", {}).get("accumulate", {})
    prog = red["programs"].get("accumulate")
    hit = [phases[p]["s"] for p in GRAD_PHASES if p in phases]
    if not hit or not prog or not prog["n"]:
        return None
    return 1e3 * sum(hit) / prog["n"]
