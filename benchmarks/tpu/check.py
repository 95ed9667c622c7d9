"""How ``correct`` is decided: the program's first steps against the plain
reference (``reference/``), each number beside its limit.

The window's own object takes the first ``check_steps`` logical steps
through one ``fit`` call during set-up; what they leave behind is read here,
once the window has closed and the program's state is freed:

* ``sample_mismatch``: over every step the run took (the checked steps and
  the window's), examples the program fetched that the reference's Poisson
  draw does not give, plus those it gives that were not fetched, plus
  physical rows that are neither (padding beyond the one partial batch of
  each step, or batches past the last step).  Exact: the limit is 0.  The
  window's ``examples_per_s`` counts the draws' examples, so this ties the
  count to the rows ``fit`` fetched.
* ``step_gap``: |the program's step counter - the logical steps the run
  drove| (limit 0): every step counted was taken.
* ``grad_gap``: the first step's gradient as the optimizer takes it, noise
  removed (DP: momentum after step 1 minus the noise the same update draws
  on a zeroed accumulator, i.e. the clipped sum over L; SGD: the mean
  gradient), against the reference's.  Per leaf, the gap between the two
  norms over the larger of the reference leaf's norm and the median leaf's;
  the worst leaf counts.  Leaves whose reference norm is under a thousandth
  of the median leaf's (a key's bias under softmax) are left out.
* ``grad_cos_gap``: 1 - the cosine between the same two gradients over
  all leaves.  The norms cannot see a fault that keeps them, such as
  altered labels on data whose labels are random; the direction can.
* ``change_gap``: the parameters' change over the checked steps, the same
  way as ``grad_gap``, the reference following each step with the noise
  the program drew.
* ``noise_std_err`` (DP): the largest over the checked steps and the step
  after them of |std of the step's noise, in units of sigma*C/L, minus 1|.
* ``noise_corr`` (DP): the largest |correlation| between the noise of two
  consecutive steps of those; a key that is not split between steps reads 1.
* ``eps_gap`` (DP): |eps the program's accountant reports after the
  checked steps - the reference RDP accountant's| / the reference's.
* ``window_compiles``: compilations inside the measured window (limit 0).

The run adds ``params_finite``: every parameter finite after the window.
"""
from __future__ import annotations

import math

import numpy as np

from reference import rdp
from reference.clipped import ClippedSum


def leaf_gaps(prog_norms, ref_norms):
    """Per leaf |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf's ‖ref‖), NaN
    for the leaves left out (reference norm under a thousandth of the
    median's)."""
    p, r = np.asarray(prog_norms), np.asarray(ref_norms)
    med = float(np.median(r))
    gaps = np.abs(p - r) / np.maximum(r, med)
    gaps[r < 1e-3 * med] = np.nan
    gaps[~np.isfinite(p)] = np.inf
    return gaps


def compare(ref_leaves, prog_leaf):
    """(per-leaf norm gaps, 1 - cosine over all leaves) of the leaves
    ``prog_leaf(i)`` against ``ref_leaves``, reduced on the device leaf by
    leaf in float32 and summed on the host in float64."""
    import jax.numpy as jnp
    rn, pn, dot = [], [], 0.0
    for i, r in enumerate(ref_leaves):
        x = prog_leaf(i)
        rn.append(float(jnp.linalg.norm(r)))
        pn.append(float(jnp.linalg.norm(x)))
        dot += float(jnp.vdot(x.astype(jnp.float32), r))
        del x
    rn, pn = np.array(rn), np.array(pn)
    denom = math.sqrt(float(np.sum(rn ** 2)) * float(np.sum(pn ** 2)))
    cos_gap = 1.0 - dot / denom if denom > 0 else math.inf
    return leaf_gaps(pn, rn), cos_gap


def sample_mismatch(fetched, drawn_steps, physical: int) -> int:
    """Count of rows where what ``fit`` fetched departs from the draws.

    ``fetched`` holds the index arrays in the order ``fit`` asked for them,
    over all steps; each step takes max(1, ceil(len(draw) / physical))
    physical batches of them, and batches past the last step count whole."""
    bad, at = 0, 0
    for drawn in drawn_steps:
        tl = len(drawn)
        nb = max(1, -(-tl // physical))
        batches = fetched[at:at + nb]
        at += nb
        rows = np.concatenate(batches) if batches else np.zeros(0, np.int64)
        bad += abs(len(rows) - nb * physical)
        n = min(len(rows), tl)
        bad += int(np.sum(rows[:n] != drawn[:n])) + (tl - n)
        bad += int(np.sum(rows[tl:] != 0))
    return bad + sum(len(x) for x in fetched[at:])


def noise_stats(noise, unit: float, size: int):
    """(largest |std / unit - 1|, largest |correlation| of consecutive
    steps, infinite where one is all zeros) over the flat noise arrays of
    consecutive steps."""
    import jax.numpy as jnp
    std_err, corr, prev = 0.0, 0.0, None
    for z in noise:
        z = jnp.asarray(z[:size])
        std_err = max(std_err, abs(float(jnp.std(z)) / unit - 1.0))
        if prev is not None:
            d = float(jnp.linalg.norm(z)) * float(jnp.linalg.norm(prev))
            c = abs(float(jnp.vdot(z, prev))) / d if d > 0 else math.inf
            corr = max(corr, c)
        prev = z
    return std_err, corr


def readings(run: dict, cell: dict, config: dict, pool, make_params,
             draw, control: bool = False) -> dict:
    """The compared numbers of one run.  With ``control``, also those of
    the control, under ``"control"``: the reference with int8 matmul
    operands in the program's place (``grad_gap``, ``grad_cos_gap``,
    ``change_gap``), and the accountant in float32 (``eps_gap``), against
    the float32 and float64 references.

    ``run`` holds what the program left: ``fetched`` (index arrays in
    fetch order, over ``steps`` logical steps), ``step_gap``, ``mom1``
    (flat momentum after step 1), ``noise`` (per checked step and the step
    after them, the flat noise sigma*C*z/L the update drew), ``params_k``
    (tree after the checked steps, host), ``eps``, ``sigma``, ``compiles``.
    Under ``"detail"``: the worst leaf of each gap and the median leaf's
    gap, which are printed and not compared."""
    import jax
    import jax.numpy as jnp

    K = cell["check_steps"]
    n, L = cell["n_data"], float(cell["expected_batch"])
    q = L / n
    private = cell["engine"] != "nonprivate"
    mu, lr = cell["momentum"], cell["lr"]
    out = {}
    draws = [draw(run["seed"], k, n, q) for k in range(run["steps"])]
    out["sample_mismatch"] = sample_mismatch(run["fetched"], draws,
                                             cell["physical_batch"])
    out["step_gap"] = run["step_gap"]

    params0 = make_params()
    shapes = [x.shape for x in jax.tree.leaves(params0)]
    sizes = [int(np.prod(sh)) for sh in shapes]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    paths = list(run["leaf_shapes"])

    def noise_leaf(k, i):
        return run["noise"][k][offsets[i]:offsets[i + 1]].reshape(shapes[i])

    def prog_leaf(i):
        """Leaf i of the first step's gradient as the optimizer took it,
        noise removed, on the device (one leaf at a time: the cell's
        state may fill most of the host's memory)."""
        g = run["mom1"][offsets[i]:offsets[i + 1]]
        if private:
            g = g - run["noise"][0][offsets[i]:offsets[i + 1]]
        return jnp.asarray(g.reshape(shapes[i]))

    def descend(clipped, first):
        """The checked steps by ``clipped``, each followed with the noise
        the program drew; ``first`` gets step 1's gradient leaves.
        Returns the parameters after them."""
        p, m = params0, None
        for k in range(K):
            rows = pool.rows(draws[k])
            denom = L if private else max(len(draws[k]), 1)
            g = jax.tree.map(lambda x, d=denom: x / d, clipped(p, rows))
            if k == 0:
                first(jax.tree.leaves(g))
            if private:
                g = jax.tree.unflatten(jax.tree.structure(p), [
                    x + jnp.asarray(noise_leaf(k, i))
                    for i, x in enumerate(jax.tree.leaves(g))])
            m = g if m is None else jax.tree.map(lambda a, b: mu * a + b,
                                                 m, g)
            del g
            p = jax.tree.map(lambda a, b: a - lr * b, p, m)
        return p

    def clipped_sum(int8=False):
        return ClippedSum(config["reference"], config["model"],
                          private=private, clip_norm=cell["clip_norm"],
                          block=cell["ref_block"], int8=int8)

    detail, ctl_out, kept = {}, {}, {}

    def first_ref(ref_leaves):
        gaps, cos_gap = compare(ref_leaves, prog_leaf)
        out["grad_gap"] = float(np.nanmax(gaps))
        out["grad_cos_gap"] = cos_gap
        detail["grad_worst_leaf"] = paths[int(np.nanargmax(gaps))]
        detail["grad_median_leaf_gap"] = float(np.nanmedian(gaps))
        if control:
            kept["grad"] = [np.asarray(x) for x in ref_leaves]

    p = jax.tree.leaves(descend(clipped_sum(), first_ref))
    p0 = jax.tree.leaves(params0)
    d_ref = [a - b for a, b in zip(p, p0)]
    del p
    pk = jax.tree.leaves(run["params_k"])
    gaps, _ = compare(d_ref, lambda i: jnp.asarray(pk[i]) - p0[i])
    out["change_gap"] = float(np.nanmax(gaps))
    detail["change_worst_leaf"] = paths[int(np.nanargmax(gaps))]
    detail["change_median_leaf_gap"] = float(np.nanmedian(gaps))
    if control:
        kept["change"] = [np.asarray(x) for x in d_ref]
    del d_ref

    if control:
        def first_ctl(ctl_leaves):
            gaps, cos_gap = compare(kept.pop("grad"),
                                    lambda i: ctl_leaves[i])
            ctl_out["grad_gap"] = float(np.nanmax(gaps))
            ctl_out["grad_cos_gap"] = cos_gap

        pc = jax.tree.leaves(descend(clipped_sum(int8=True), first_ctl))
        gaps, _ = compare(kept.pop("change"), lambda i: pc[i] - p0[i])
        ctl_out["change_gap"] = float(np.nanmax(gaps))
        del pc
    del p0, params0

    if private:
        unit = run["sigma"] * cell["clip_norm"] / L
        out["noise_std_err"], out["noise_corr"] = noise_stats(
            run["noise"], unit, int(offsets[-1]))
        delta = 1.0 / (10 * n)
        e_ref = rdp.epsilon(q, run["sigma"], K, delta)
        out["eps_gap"] = abs(run["eps"] - e_ref) / e_ref
        if control:
            e32 = rdp.epsilon(q, run["sigma"], K, delta, np.float32)
            ctl_out["eps_gap"] = abs(e32 - e_ref) / e_ref
    if "compiles" in run:
        out["window_compiles"] = run["compiles"]
    if control:
        out["control"] = ctl_out
    out["detail"] = detail
    return out


def verdict(values: dict, limits: dict, only=None):
    """(correct, [(name, value, limit)]) over the numbers the cell holds
    limits for; a number that has no limit, or a limit with no number,
    fails.  With ``only``, just those names are judged (the control's
    numbers, or a run without a window)."""
    rows, ok = [], True
    names = set(values) | set(limits) if only is None else set(only)
    for name in sorted(names, key=lambda k: (k not in values, k)):
        if name in ("detail", "control"):
            continue
        v, lim = values.get(name), limits.get(name)
        good = lim is not None and v is not None and math.isfinite(v) \
            and v <= lim
        ok = ok and good
        rows.append((name, v, lim))
    return ok, rows
