"""Analytic roofline cost model: FLOPs, HBM bytes, collective bytes per
(arch x input-shape x engine x mesh).

Why analytic: XLA's cost_analysis() counts a lax.scan body ONCE (verified in
EXPERIMENTS.md §Dry-run), so scanned-layer HLO underreports by ~n_layers.  We
therefore derive costs from the parameter tree (exact leaf shapes via
eval_shape — no hand-written N formulas) plus per-family attention/SSD terms,
and CROSS-VALIDATE against exact fully-unrolled HLO on small configs
(tests/test_costmodel.py, EXPERIMENTS.md §Roofline).

Engine multipliers over the forward matmul cost F (per physical batch):
    nonprivate   1F fwd + 2F bwd                                   = 3F
    masked_pe    same graph under vmap                              = 3F
                 (+ per-example grad write/read: 2·B·N bytes!)
    masked_ghost 2 passes: (fwd + dX) + (fwd + dX + dW) + norms     = 5F + norms
    masked_bk    fwd + dX + analytic dW + norms                     = 3F + norms
Ghost-norm flops per dense: B · min(2·T²·(di+do), 2·T·di·do)  (mixed rule).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, InputShape
from ..utils.params import flatten_params
from .mesh import HBM_BYTES


@dataclasses.dataclass
class Costs:
    flops: float            # global FLOPs per step
    hbm_bytes: float        # global HBM traffic per step
    coll_bytes: float       # per-device collective bytes per step
    model_flops: float      # 6·N_active·tokens (the "useful" floor)
    n_params: float
    n_active: float
    detail: Dict[str, float]


def param_stats(model, cfg: ArchConfig):
    """Exact param counts from the tree (experts discounted by top_k/E for
    the active count)."""
    shapes = jax.eval_shape(                   # lint: allow-const-key
        lambda: model.init(jax.random.PRNGKey(0)))
    flat = flatten_params(shapes)
    total = 0.0
    active = 0.0
    for path, leaf in flat.items():
        n = float(math.prod(leaf.shape))
        total += n
        if ".moe.w" in path.replace("/", "."):
            active += n * (cfg.top_k / max(cfg.n_experts, 1))
        else:
            active += n
    return total, active, flat


def _dense_fwd_flops(flat, cfg: ArchConfig, tokens: float) -> float:
    """2 · rows · i · o over every matmul leaf (experts use effective rows)."""
    f = 0.0
    for path, leaf in flat.items():
        sh = leaf.shape
        if len(sh) < 2 or min(sh[-2:]) < 8:
            continue        # vectors/norms
        stack = math.prod(sh[:-2]) if len(sh) > 2 else 1
        i, o = sh[-2], sh[-1]
        if ".moe.w" in path:
            # stacked (L, E, i, o): each expert sees tokens·K·cf/E rows
            L = math.prod(sh[:-3]) if len(sh) > 3 else 1
            rows = tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts
            f += 2 * L * cfg.n_experts * rows * i * o
        elif path.startswith("emb"):
            continue        # gather, not matmul
        else:
            f += 2 * stack * tokens * i * o
    return f


def _attn_fwd_flops(cfg: ArchConfig, B: float, T: float) -> float:
    """Softmax-attention score+context flops (full materialised, causal)."""
    fam = cfg.family
    hd = cfg.hd
    if fam in ("dense", "moe", "vit"):
        n_attn = cfg.n_layers
        Tk = T if not cfg.sliding_window else min(T, cfg.sliding_window)
        return 4.0 * n_attn * B * T * Tk * cfg.n_heads * hd
    if fam == "vlm":
        n_sup = cfg.n_layers // cfg.cross_every
        self_l = n_sup * (cfg.cross_every - 1)
        cross = 4.0 * n_sup * B * T * cfg.n_image_tokens * cfg.n_heads * hd
        return 4.0 * self_l * B * T * T * cfg.n_heads * hd + cross
    if fam == "audio":
        ne = cfg.n_encoder_layers or cfg.n_layers
        Ta = cfg.n_audio_frames
        enc = 4.0 * ne * B * Ta * Ta * cfg.n_heads * hd
        dec = 4.0 * cfg.n_layers * B * T * T * cfg.n_heads * hd
        cross = 4.0 * cfg.n_layers * B * T * Ta * cfg.n_heads * hd
        return enc + dec + cross
    if fam == "hybrid":
        n_att = cfg.n_layers // cfg.attn_every
        return 4.0 * n_att * B * T * T * cfg.n_heads * hd + \
            _ssd_flops(cfg, B, T, cfg.n_layers)
    if fam == "ssm":
        return _ssd_flops(cfg, B, T, cfg.n_layers)
    return 0.0


def _ssd_flops(cfg: ArchConfig, B: float, T: float, n_ssm: int) -> float:
    """Chunked SSD: intra-chunk quadratic + state terms."""
    Q = min(cfg.ssm_chunk, T)
    H, P, N = cfg.nheads_ssm, cfg.ssm_head_dim, cfg.ssm_state
    nc = max(T // Q, 1)
    per_layer = (2 * B * nc * Q * Q * N          # C·Bᵀ
                 + 2 * B * nc * Q * Q * H * P    # intra combine
                 + 4 * B * T * N * H * P)        # states in/out
    return n_ssm * per_layer


def _ghost_norm_flops(flat, cfg: ArchConfig, B: float, T: float) -> float:
    f = 0.0
    for path, leaf in flat.items():
        sh = leaf.shape
        if len(sh) < 2 or min(sh[-2:]) < 8 or path.startswith("emb"):
            continue
        stack = math.prod(sh[:-2]) if len(sh) > 2 else 1
        i, o = sh[-2], sh[-1]
        Te = T
        if ".moe.w" in path:
            Te = T * cfg.top_k * cfg.capacity_factor / max(cfg.n_experts, 1)
            stack = math.prod(sh[:-2])
        f += stack * B * min(2 * Te * Te * (i + o), 2 * Te * i * o)
    # embedding ghost: B·T²·d
    f += 2 * B * T * T * cfg.d_model
    return f


ENGINE_MM_MULT = {"nonprivate": 3.0, "pe": 3.0, "masked_pe": 3.0,
                  "masked_fused": 3.0, "masked_fused_stream": 3.0,
                  "masked_ghost": 5.0, "masked_bk": 3.0}
ENGINE_ATTN_MULT = {"nonprivate": 3.0, "pe": 3.0, "masked_pe": 3.0,
                    "masked_fused": 3.0, "masked_fused_stream": 3.0,
                    "masked_ghost": 5.0, "masked_bk": 3.0}

# streaming engine: live bytes the tile sizing must keep under budget beyond
# the per-example slab and the train state — the flat f32 accumulator carry
# plus one params-sized f32 live buffer (the summed-tile output the aliased
# kernel writes through)
STREAM_FIXED_F32_BUFFERS = 2
# copies of one example's params-sized gradient row live at the peak: the
# vmapped gradient tree, its flat concatenation, the tile padded to the
# accumulator layout and the layer-stacked backward's output.  Read off
# memory_analysis() of the full-width vit-base accumulate compiled for TPU
# v5e: temp grew 1.27-1.39 GB per tile row at m <= 4, i.e. 3.7-4.0 rows of
# 0.34 GB each (fewer at m = 16, so 4 is the safe side)
STREAM_ROW_COPIES = 4
# rows of one TPU vreg tile: the second-minor dim of an array pads to this
SUBLANES = 8


def stream_tile_size(batch_size: int, n_params: int,
                     budget_bytes: float = HBM_BYTES,
                     pe_dtype_bytes: int = 4,
                     state_bytes: float = 0.0) -> int:
    """Largest streaming tile m ≤ batch whose live state fits the budget.

    Peak live memory of the scanned clip-and-accumulate is
    ``STREAM_ROW_COPIES · m · n_params · pe_dtype_bytes`` (the tile's
    per-example grads and their copies) plus
    :data:`STREAM_FIXED_F32_BUFFERS` params-sized f32 buffers — the
    O(m·params + params) the streaming engine exists for — plus
    ``state_bytes``, the train state held live around the step (the caller
    counts it: params, optimizer state and accumulator, in and out).

    A tile cut below the batch is rounded down to a multiple of
    :data:`SUBLANES`: the TPU lays an (m, D) array out in 8-row tiles, so
    m = 10 holds as much as m = 16 (the v5e compile at vit-base widths put
    19.7 GB of temp at m = 10 against 7.3 GB at m = 8).  Pure arithmetic
    (no jax), so sessions can size tiles at config time and dry-runs can
    price meshes far larger than the host."""
    fixed = state_bytes + STREAM_FIXED_F32_BUFFERS * 4.0 * n_params
    free = budget_bytes - fixed
    if free <= 0:
        return 1
    m = int(free // max(STREAM_ROW_COPIES * n_params * pe_dtype_bytes, 1))
    if m >= batch_size:
        return int(batch_size)
    if m >= SUBLANES:
        m -= m % SUBLANES
    return max(1, m)


def train_costs(model, cfg: ArchConfig, shape: InputShape, engine: str,
                mesh_shape: Dict[str, int], dtype_bytes: int = 2) -> Costs:
    B, T = float(shape.global_batch), float(shape.seq_len)
    tokens = B * T
    n, n_active, flat = param_stats(model, cfg)
    chips = math.prod(mesh_shape.values())
    dshard = mesh_shape.get("data", 1) * mesh_shape.get("pod", 1)
    mshard = mesh_shape.get("model", 1)

    Fmm = _dense_fwd_flops(flat, cfg, tokens)
    Fattn = _attn_fwd_flops(cfg, B, T)
    mult = ENGINE_MM_MULT[engine]
    norms = _ghost_norm_flops(flat, cfg, B, T) \
        if engine in ("masked_ghost", "masked_bk") else 0.0
    flops = mult * (Fmm + Fattn) + norms

    # ---- HBM bytes (global) ----
    # params: fwd read + bwd read + grad write/read + opt update (f32 state)
    p_bytes = n * (2 * dtype_bytes + 4 * 4)
    # activations: ~6 tensors of (B,T,d) per layer (records for ghost/bk)
    act_coeff = {"nonprivate": 4, "pe": 6, "masked_pe": 6, "masked_fused": 6,
                 "masked_fused_stream": 6, "masked_ghost": 12,
                 "masked_bk": 10}[engine]
    acts = act_coeff * tokens * cfg.d_model * max(cfg.n_layers, 1) * dtype_bytes
    # attention scores traffic (write+read of (B,H,T,Tk))
    Tk = T if not cfg.sliding_window else min(T, cfg.sliding_window)
    if cfg.family in ("dense", "moe", "vlm", "vit", "audio"):
        scores = 2 * cfg.n_layers * B * cfg.n_heads * T * Tk * dtype_bytes
    elif cfg.family == "hybrid":
        scores = 2 * (cfg.n_layers // cfg.attn_every) * B * cfg.n_heads * T * T * dtype_bytes
    else:
        scores = 0.0
    # per-example grads (the pe engines' memory wall): write + read of B·N
    # (masked_fused materialises them too — its kernel fuses only the
    # clip+accumulate re-read, one of the two passes.  masked_fused_stream
    # has the same TRAFFIC — every tile's grads are still written+read once,
    # summing to 2·B·N over the scan — its win is peak LIVE memory, which
    # stream_tile_size models, not bytes moved)
    pe_bytes = 2 * B * n * 4 \
        if engine in ("pe", "masked_pe", "masked_fused",
                      "masked_fused_stream") else 0.0
    hbm = p_bytes + acts + scores + pe_bytes

    # ---- collective bytes (per device) ----
    # FSDP weight all-gathers: each device receives the full (TP-sharded)
    # weight set once per pass; passes: fwd+bwd(+ghost 2nd pass).  The
    # streaming engine re-gathers per scanned tile under FSDP (n_tiles·2);
    # that is not modelled here — dp/dp_sp keep params replicated, and the
    # table stays static per engine.
    passes = {"nonprivate": 2, "pe": 2, "masked_pe": 2, "masked_fused": 2,
              "masked_fused_stream": 2, "masked_ghost": 4,
              "masked_bk": 2}[engine]
    ag_w = passes * (n / mshard) * dtype_bytes * (dshard - 1) / dshard
    # grad all-reduce over data (ring: 2x per byte)
    ar_g = 2 * (n / mshard) * 4 * (dshard - 1) / dshard
    # TP activation psums: ~4 per layer per pass over (B_loc, T, D)
    b_loc = B / dshard
    tp = 4 * passes * max(cfg.n_layers, 1) * b_loc * T * cfg.d_model \
        * dtype_bytes * (mshard - 1) / mshard
    # MoE all-to-all (dispatch+combine, fwd+bwd)
    a2a = 0.0
    if cfg.n_experts:
        a2a = 4 * b_loc * T * cfg.top_k * cfg.capacity_factor * cfg.d_model \
            * dtype_bytes
    coll = ag_w + ar_g + tp + a2a

    return Costs(flops=flops, hbm_bytes=hbm, coll_bytes=coll,
                 model_flops=6.0 * n_active * tokens, n_params=n,
                 n_active=n_active,
                 detail={"mm_fwd": Fmm, "attn_fwd": Fattn, "norms": norms,
                         "ag_w": ag_w, "ar_g": ar_g, "tp": tp, "a2a": a2a,
                         "pe_bytes": pe_bytes, "acts": acts})


def decode_costs(model, cfg: ArchConfig, shape: InputShape,
                 mesh_shape: Dict[str, int], dtype_bytes: int = 2) -> Costs:
    """One-token serve_step with a cache of length S."""
    B, S = float(shape.global_batch), float(shape.seq_len)
    n, n_active, flat = param_stats(model, cfg)
    chips = math.prod(mesh_shape.values())
    dshard = mesh_shape.get("data", 1) * mesh_shape.get("pod", 1)
    mshard = mesh_shape.get("model", 1)

    flops = 2.0 * n_active * B
    # attention reads over the cache
    hd = cfg.hd
    kvh = max(cfg.n_kv_heads, 1)
    Sk = min(S, cfg.sliding_window) if cfg.sliding_window else S
    if cfg.family in ("dense", "vlm", "audio"):
        flops += 4.0 * cfg.n_layers * B * Sk * cfg.n_heads * hd
        cache = 2 * cfg.n_layers * B * Sk * kvh * hd * dtype_bytes
    elif cfg.family == "moe":
        if cfg.kv_lora:
            flops += 2.0 * cfg.n_layers * B * Sk * (cfg.kv_lora + cfg.rope_dim) * cfg.n_heads
            cache = cfg.n_layers * B * Sk * (cfg.kv_lora + cfg.rope_dim) * dtype_bytes
        else:
            flops += 4.0 * cfg.n_layers * B * Sk * cfg.n_heads * hd
            cache = 2 * cfg.n_layers * B * Sk * kvh * hd * dtype_bytes
    elif cfg.family == "ssm":
        H, P, N = cfg.nheads_ssm, cfg.ssm_head_dim, cfg.ssm_state
        flops += 4.0 * cfg.n_layers * B * H * N * P
        cache = cfg.n_layers * B * H * N * P * 4
    else:  # hybrid
        H, P, N = cfg.nheads_ssm, cfg.ssm_head_dim, cfg.ssm_state
        n_att = cfg.n_layers // cfg.attn_every
        flops += 4.0 * cfg.n_layers * B * H * N * P
        flops += 4.0 * n_att * B * Sk * cfg.n_heads * hd
        cache = (cfg.n_layers * B * H * N * P * 4
                 + 2 * n_att * B * Sk * kvh * hd * dtype_bytes)

    hbm = n_active * dtype_bytes + cache
    # collectives: TP psums on tiny (B,1,D) activations + per-step weight AG
    b_loc = max(B / dshard, 1.0)
    coll = (4 * cfg.n_layers * b_loc * cfg.d_model * dtype_bytes
            * (mshard - 1) / mshard
            + (n_active / mshard) * dtype_bytes * (dshard - 1) / dshard)
    return Costs(flops=flops, hbm_bytes=hbm, coll_bytes=coll,
                 model_flops=2.0 * n_active * B, n_params=n,
                 n_active=n_active,
                 detail={"cache_bytes": cache})
