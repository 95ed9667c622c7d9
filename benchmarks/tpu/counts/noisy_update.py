"""noisy_update, one parameter leaf: p -= lr (mu m + (acc + sigma C z)/L),
m updated in place of the old momentum (or without momentum, or without
noise: the non-private step draws no z).

Operands are (rows, 128) f32 blocks of p, acc and, with momentum, m, plus
scalars; results are p and, with momentum, m.  The least traffic reads each
block operand once and writes each result once; the noise is drawn in the
kernel and moves no bytes.  FLOPs: 3 per element for the gradient, 2 for
the momentum, 2 for the step (the draw's transcendental work is not
counted)."""

# the jitted functions whose pallas_call this file counts (the HLO op_name)
CALLERS = ("noisy_sgd_update",)


def count(operands, results):
    blocks = [(shape, b) for shape, b in operands
              if len(shape) == 2 and shape[1] == 128]
    n = blocks[0][0][0] * 128
    moved = sum(s[0] * s[1] * b for s, b in blocks) \
        + sum(s[0] * s[1] * b for s, b in results)
    flops = (7.0 if len(results) == 2 else 5.0) * n
    return flops, float(moved)
