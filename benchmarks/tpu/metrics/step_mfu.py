"""The whole step's share of the chips' peak: examples/s of the traced
window x model FLOPs per example (flops.py) / (chips x peak bf16 FLOP/s),
in percent."""


def read(red, counters, cell):
    flops = counters.get("flops_per_example")
    if not flops:
        return None
    peak = cell["peaks"]["flops_bf16"] * counters["chips"]
    return 100.0 * counters["examples_per_s"] * flops / peak
