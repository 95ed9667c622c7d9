"""The whole step's share of the chips' peak for a model whose layers hold a
share of their routed experts: examples/s of the traced window x model
FLOPs per example / (chips x peak bf16 FLOP/s), in percent.

Model FLOPs per example are counted from the configuration
(``cell["config"]["model"]``) and the row length T as ``flops.py`` counts
them, 3 x (the forward's matmuls + attention's scores and context), but
knowing experts.  The forward's matmuls, 2 x rows x in x out each:

* every layer's latent attention on T rows: wq (D x H(dn + dr)), wdkv
  (D x r), wukv (r x H(dn + dv)), wkr (D x dr), wo (H dv x D);
* the leading dense layers' SwiGLU MLP (3 x D x F_dense) on T rows;
* each expert layer's router (D x E_router) and shared experts
  (3 x D x n_shared F_e) on T rows, and its held experts' SwiGLU
  (3 x D x F_e each) on the T K held / E_router rows routed to them on
  average (drops are not subtracted);
* the head over the vocabulary slice (D x V) on T rows; no embedding.

Attention: 2 T^2 H (dn + dr) for the scores and 2 T^2 H dv for the
context, a layer, the full T x T as ``flops.py`` counts it.  dn and dv are
the configuration's ``head_dim``, dr its ``rope_dim``."""


def per_example(model: dict, T: int) -> float:
    D, H = model["d_model"], model["n_heads"]
    dn = dv = model["head_dim"]
    dr, r = model["rope_dim"], model["kv_lora"]
    E, K = model["n_experts"], model["top_k"]
    ER = model.get("n_router_experts") or E
    Fe = model.get("moe_d_ff") or model["d_ff"]
    nd = model["first_dense_layers"]
    n_moe = model["n_layers"] - nd
    attn = D * H * (dn + dr) + D * r + r * H * (dn + dv) + D * dr + H * dv * D
    dense = 3 * D * model["dense_d_ff"]
    moe = D * ER + 3 * D * model["n_shared_experts"] * Fe
    routed = 3 * D * Fe * E * K / ER
    per_token = (model["n_layers"] * attn + nd * dense
                 + n_moe * (moe + routed) + D * model["vocab"])
    scores = 2.0 * T * T * H * (dn + dr + dv) * model["n_layers"]
    return 3.0 * (2.0 * T * per_token + scores)


def read(red, counters, cell):
    model, T = cell["config"]["model"], cell["seq_len"]
    if not model.get("n_experts") or not model.get("kv_lora") or not T:
        return None
    peak = cell["peaks"]["flops_bf16"] * counters["chips"]
    return 100.0 * counters["examples_per_s"] * per_example(model, T) / peak
