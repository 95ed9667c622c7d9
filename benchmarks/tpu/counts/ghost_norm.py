"""ghost_norm: n_b = || X_b^T dY_b ||_F^2 for one dense layer, the T axis
padded to the kernel's slab.

Operands x (B, T, din) and dy (B, T, dout) in f32; result (B, 8, 128).
The least work forms every X_b^T dY_b product (2 B T din dout) and squares
and sums it (2 B din dout); the least traffic reads x and dy once and
writes the result."""

# the jitted functions whose pallas_call this file counts (the HLO op_name)
CALLERS = ("ghost_norm_dense",)


def count(operands, results):
    (B, T, di), xb = operands[0]
    (_, _, do), yb = operands[1]
    flops = 2.0 * B * T * di * do + 2.0 * B * di * do
    rs, rb = results[0]
    bytes_ = B * T * (di * xb + do * yb) + rs[0] * rs[1] * rs[2] * rb
    return flops, float(bytes_)
