"""DeepSeek-V2 at a test size on the CPU: the program's ``DeepseekV2LM``
against the plain reference (``reference/deepseek_v2.py``) on seeded
random weights, YaRN pinned at the published rope dims, one chip's share of
the experts against the whole layer, each row's clipped gradient against
changes to another row, the model's block scopes in the compiled program,
and the test-size cell through the harness.

The test model (``fixtures/moe/configs/tiny-moe.json``) keeps what the
published model does differently from the others: YaRN, gates that are not
renormalised, a float32 router, shared experts, a leading dense layer, and
a share (experts 2-3 of 8) with a capacity small enough that assignments
drop."""
import json
import math
import os
import re

import numpy as np
import pytest

from _paths import FIXTURES

import cell
import check
import control
import phase_split
from reference import deepseek_v2 as ref
from reference.clipped import ClippedSum
from reference.common import Numerics

MOE = os.path.join(FIXTURES, "moe")
SEED = 2**31 + 77
B, T = 4, 16


def _tiny(**over):
    with open(os.path.join(MOE, "configs", "tiny-moe.json")) as f:
        return dict(json.load(f)["model"], **over)


def _program(model: dict):
    from repro.configs.base import ArchConfig
    from repro.models import build
    return build(ArchConfig(**model))


def _setup(model: dict, key=0):
    import jax
    prog = _program(model)
    params = prog.init(jax.random.PRNGKey(key))
    ks = jax.random.split(jax.random.PRNGKey(key + 1), 2)
    batch = {"tokens": jax.random.randint(ks[0], (B, T), 0, model["vocab"]),
             "labels": jax.random.randint(ks[1], (B, T), 0, model["vocab"])}
    return prog, params, batch


# -- YaRN at the published rope dims ------------------------------------------

def test_yarn_pins():
    """DeepSeek-V2-Lite's rope: dim 64, theta 1e4, factor 40 over 4096
    positions, beta 32/1, mscale = mscale_all_dim = 0.707."""
    from repro.configs.deepseek_v2_lite_16b import CONFIG
    from repro.models import common as cm
    from repro.models.mla import rope_scaling
    assert cm.yarn_correction_range(64, 1e4, 4096, 32, 1) == (10, 23)
    plain = np.asarray(cm.rope_freqs(64, 1e4))
    model = {k: getattr(CONFIG, k) for k in (
        "rope_theta", "rope_scaling_factor", "rope_original_max_pos",
        "rope_beta_fast", "rope_beta_slow", "rope_mscale",
        "rope_mscale_all_dim")}
    ref_inv, ref_cs, ref_score = ref.yarn(64, model)
    prog_inv, prog_cs, prog_scale = rope_scaling(CONFIG)
    for inv in (np.asarray(prog_inv), np.asarray(ref_inv)):
        # pairs up to low keep their frequency, from high on it is / 40
        assert inv[9] == pytest.approx(plain[9], rel=1e-6)
        assert inv[10] == pytest.approx(plain[10], rel=1e-6)
        assert inv[23] == pytest.approx(plain[23] / 40, rel=1e-6)
        assert inv[24] == pytest.approx(plain[24] / 40, rel=1e-6)
        # between them, the linear ramp
        r = (16 - 10) / 13
        assert inv[16] == pytest.approx(
            plain[16] * (1 - r) + plain[16] / 40 * r, rel=1e-6)
    assert prog_cs == ref_cs == 1.0
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert prog_scale == pytest.approx(192 ** -0.5 * mscale ** 2, rel=1e-12)
    assert prog_scale == pytest.approx(0.114724, abs=5e-6)
    assert ref_score * 192 ** -0.5 == pytest.approx(prog_scale, rel=1e-12)


# -- the program against the reference ----------------------------------------

def _drops(model: dict, params, batch) -> int:
    """Assignments to held experts that the capacity drops, over the expert
    layers of the reference's forward (the test must see some)."""
    import jax
    import jax.numpy as jnp
    num = Numerics()
    x = params["emb"]["w"][batch["tokens"]]
    E, ER, K = model["n_experts"], model["n_router_experts"], model["top_k"]
    off = model["expert_offset"]
    cap = math.ceil(T * K * model["capacity_factor"] / ER)
    dropped = 0
    for name in ("dense_blocks", "moe_blocks"):
        blocks = params[name]
        for l in range(jax.tree.leaves(blocks)[0].shape[0]):
            blk = jax.tree.map(lambda a, l=l: a[l], blocks)
            h = ref.rmsnorm(x, blk["ln1"]["w"], ref.NORM_EPS)
            x = x + ref._attention(h, blk["attn"], model, num)
            h = ref.rmsnorm(x, blk["ln2"]["w"], ref.NORM_EPS)
            if name == "dense_blocks":
                x = x + ref._swiglu(h, blk["mlp"], num)
                continue
            routed, _ = ref._experts(h, blk["moe"], model, num)
            x = x + routed + ref._swiglu(h, blk["shared"], num)
            topi = jax.lax.top_k(h @ blk["moe"]["router"]["w"], K)[1]
            counts = jnp.sum(jax.nn.one_hot(topi, ER), axis=(1, 2))
            held = counts[:, off:off + E]
            dropped += int(jnp.sum(jnp.maximum(held - cap, 0)))
    return dropped


def test_losses_match_reference():
    """Per-example losses, float32 on both sides: they differ only in the
    order of sums (attention in one contraction over (nope + rope) dims
    against two, experts dispatched into capacity buffers against every
    expert on every token), so to a few float32 ulps of the loss."""
    import jax
    from repro.core.tape import Tape
    model = _tiny()
    prog, params, batch = _setup(model)
    assert _drops(model, params, batch) > 0
    got = np.asarray(jax.jit(lambda p, b: prog.loss(p, b, Tape()))(
        params, batch))
    want = np.asarray(jax.jit(lambda p, b: ref.loss(p, b, model, Numerics()))(
        params, batch))
    np.testing.assert_allclose(got, want, rtol=2e-6)


@pytest.mark.parametrize("engine", ["masked_ghost", "masked_pe"])
def test_clipped_sum_matches_reference(engine):
    """The clipped gradient sum of a physical batch with one row masked
    out, through the engine, against the reference's (per-example
    gradients, clipped, summed): float32 on both sides, so each leaf's norm
    gap stays under 1e-4 of the larger of its norm and the median leaf's
    (the ghost norms differ from the materialised ones only in the order of
    sums) and the direction agrees to 1e-6."""
    import jax
    import jax.numpy as jnp
    from repro.core import clipping as C
    model = _tiny()
    prog, params, batch = _setup(model, key=3)
    mask = jnp.array([1.0, 1.0, 0.0, 1.0])
    clip = 0.05           # below every row's norm: each row is clipped
    fn = jax.jit(lambda p, b, m: C.ENGINES[engine](
        lambda p, b, t: prog.loss(p, b, t), p, b, m, clip))
    got, aux = fn(params, batch, mask)
    assert float(jnp.min(aux["per_example_norms"])) > clip
    rows = {k: np.asarray(v)[np.asarray(mask) > 0] for k, v in batch.items()}
    want = ClippedSum("deepseek_v2", model, private=True, clip_norm=clip,
                      block=B)(params, rows)
    gaps, cos_gap = check.compare(jax.tree.leaves(want),
                                  lambda i: jax.tree.leaves(got)[i])
    assert np.nanmax(gaps) < 1e-4, np.nanmax(gaps)
    assert cos_gap < 1e-6


# -- one chip's share of the experts ------------------------------------------

def test_expert_shares_add_up_to_whole_layer():
    """Every share of 2 of the 8 experts, as the program's layer computes
    it, plus the shared experts once, gives the reference's uncut layer;
    the load-balance term is over the whole router on every share, so it
    is the uncut layer's, counted once."""
    import jax
    from repro.configs.base import ArchConfig
    from repro.core.tape import Tape
    from repro.models import common as cm
    from repro.models.moe import moe_block
    whole = _tiny(n_experts=8, n_router_experts=0, expert_offset=0)
    params = _program(whole).init(jax.random.PRNGKey(5))
    p = jax.tree.map(lambda a: a[0], params["moe_blocks"])
    h = jax.random.normal(jax.random.PRNGKey(6), (B, T, whole["d_model"]))
    want, want_aux = ref._experts(h, p["moe"], whole, Numerics())
    want = want + ref._swiglu(h, p["shared"], Numerics())
    total = cm.swiglu(Tape(), "shared", "-", p["shared"], h)
    for off in range(0, 8, 2):
        share = ArchConfig(**_tiny(n_experts=2, n_router_experts=8,
                                   expert_offset=off))
        held = dict(p["moe"], **{k: {"w": p["moe"][k]["w"][off:off + 2]}
                                 for k in ("w1", "w2", "w3")})
        y, aux = moe_block(Tape(), "moe", "-", held, h, share)
        np.testing.assert_allclose(np.asarray(aux), np.asarray(want_aux),
                                   rtol=1e-6)
        total = total + y
    # float32 both sides; the sums run in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("engine", ["masked_ghost", "masked_fused_stream"])
def test_rows_do_not_see_each_other(engine):
    """Row 0's per-example norm and clipped gradient, bitwise, whatever row
    2 holds: each row's capacity is its own, so another row's tokens cannot
    take its experts' slots (a batch-wide capacity would)."""
    import jax
    import jax.numpy as jnp
    from repro.core import clipping as C
    model = _tiny()
    prog, params, batch = _setup(model, key=7)
    kw = {"tile": B} if engine == "masked_fused_stream" else {}
    fn = jax.jit(lambda p, b, m: C.ENGINES[engine](
        lambda p, b, t: prog.loss(p, b, t), p, b, m, 0.05, **kw))
    only0 = jnp.array([1.0, 0.0, 0.0, 0.0])
    other = dict(batch)
    other["tokens"] = batch["tokens"].at[2].set(batch["tokens"][2][::-1])
    other["labels"] = batch["labels"].at[2].set(0)
    g1, a1 = fn(params, batch, only0)
    g2, a2 = fn(params, other, only0)
    n1, n2 = np.asarray(a1["per_example_norms"]), np.asarray(a2["per_example_norms"])
    assert n1[0] == n2[0] and n1[2] != n2[2]
    for x, y in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- the block scopes ---------------------------------------------------------

def _accumulate_hlo(arch: str) -> str:
    import numpy as np
    from repro.core import DPConfig
    from repro.core.session import PrivacySession, TrainConfig
    tc = TrainConfig(n_data=64, seq_len=8, physical_batch=2, q=0.1,
                     smoke=True)
    s = PrivacySession.from_config(arch, DPConfig(engine="masked_ghost"), tc)
    v = s.model_cfg.vocab
    batch = {"tokens": np.arange(16, dtype=np.int32).reshape(2, 8) % v,
             "labels": np.ones((2, 8), np.int32)}
    return s.compiled("accumulate", batch, np.ones(2, np.float32)).as_text()


_BLOCK = re.compile(r"(?:^|(?<=[/(]))(?:mla/attention|moe/route|moe/dispatch|"
                    r"moe/experts|moe/combine)(?:/|(?=\)))")


def test_block_scopes_in_the_compiled_program():
    """The DeepSeek accumulate names every block of ``BLOCKS`` in its
    instructions' ``op_name``; each instruction keeps the phase it has with
    the block scopes taken out; a dense model's names none."""
    from repro.obs.phases import BLOCKS
    text = _accumulate_hlo("deepseek-v2-lite-16b")
    names = re.findall(r'op_name="([^"]*)"', text)
    for b in BLOCKS:
        assert any(re.search(rf"(^|[/(]){re.escape(b)}($|[/)])", n)
                   for n in names), b
    stripped = re.sub(r'op_name="([^"]*)"',
                      lambda m: f'op_name="{_BLOCK.sub("", m.group(1))}"',
                      text)
    assert stripped != text
    assert phase_split.op_phases(stripped) == phase_split.op_phases(text)
    # the blocks run inside the engine's phases
    inside = {phase_split.innermost_phase(n) for n in names
              if any(b in n for b in BLOCKS)}
    assert {"grad/norm_pass", "grad/reweighted"} <= inside <= {
        "grad/norm_pass", "grad/reweighted", None}
    dense = _accumulate_hlo("qwen2-0.5b")
    assert not any(b in dense for b in BLOCKS)


def test_block_refuses_other_names():
    from repro.obs.phases import block
    with pytest.raises(ValueError, match="unknown block"):
        block("moe/gate")


# -- the test-size cell through the harness -----------------------------------

@pytest.mark.parametrize("fault", [None, "half_batch", "token"])
def test_moe_cell_correct_only_when_sound(fault):
    spec = cell.load_spec("tiny-moe.dp-ghost", MOE, MOE)
    with control.planted(fault, spec):
        result = cell.run_cell(spec, SEED, 0.3, False, want_chip=False)
    assert result["correct"] is (fault is None), result["check"]


def test_step_mfu_routed_counts_experts():
    """The reader's FLOPs at the benchmark's configuration: 2.01 TFLOP a
    1,024-token row, the held experts at 6 x 8/64 of the tokens."""
    from _paths import BENCH
    with open(os.path.join(BENCH, "configs", "deepseek-v2-lite.json")) as f:
        model = json.load(f)["model"]
    read = cell.load_module("metrics", "step_mfu_routed")
    assert read.per_example(model, 1024) == pytest.approx(2.0096e12,
                                                          rel=1e-4)
    peaks = {"flops_bf16": 197e12}
    got = read.read({}, {"examples_per_s": 10.0, "chips": 1},
                    {"config": {"model": model}, "seq_len": 1024,
                     "peaks": peaks})
    assert got == pytest.approx(100 * 10 * 2.0096e12 / 197e12, rel=1e-4)
    # a model without experts is not this reader's
    assert read.read({}, {"examples_per_s": 10.0, "chips": 1},
                     {"config": {"model": {"n_experts": 0}},
                      "seq_len": 1024, "peaks": peaks}) is None
