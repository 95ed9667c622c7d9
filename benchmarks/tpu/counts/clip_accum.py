"""clip_accum: out (D,) = [acc +] sum_b mask_b min(1, C/n_b) g_b.

Operands: the rows g (m, D) in their storage dtype, norms and mask (m, 1),
C and a count, and in the streaming form (``clip_accum_inplace``) the
accumulator acc (1, D) f32, updated in place.  The least traffic reads
each row of g once, reads acc once and writes the result once; per element
one multiply and one add."""

# the jitted functions whose pallas_call this file counts (the HLO op_name)
CALLERS = ("clip_accum_inplace", "clip_accum")


def count(operands, results):
    D = results[0][0][-1]
    (m, _), gbytes = max(operands, key=lambda o: o[0][0] * o[0][-1]
                         if len(o[0]) == 2 else 0)
    has_acc = any(shape == (1, D) for shape, _ in operands)
    flops = 2.0 * m * D
    bytes_ = m * D * gbytes + (2.0 if has_acc else 1.0) * D * 4
    return flops, bytes_
