"""The `kimi_k2` family: Kimi-K2.6's block (`ray_tpu.models.kimi_k2`:
DeepSeek-V3's; multi-head LATENT attention whose cache is ONE row of 576
values a token and layer, read in the absorbed form by both paged
kernels; a sigmoid router that chooses by a biased score and gates by
2.827 x the unbiased one over more experts than the chip holds; one
shared expert; YaRN on the 64 rotary dims), held to
harness/reference/kimi_k2_ref.py. What a family is, and what each
function is for: harness/families.py.

The configuration file holds ONE CHIP'S SHARE of a 32-chip
expert-parallel group under the keys of the public config.json:
`n_routed_experts` is the experts held (the router's width is the
published count, `published.n_routed_experts`), `vocab_size` the rows of
embedding and head held, `num_hidden_layers` the leading layers run
(`first_k_dense_replace` of them dense). `rope_scaling` is copied whole.

What a decode step must read and do is counted here, from the
configuration's own sizes at the PUBLISHED row width (bf16, 2 B a value;
a row is 576 values = 1,152 B; the chip stores it in 640 lanes, which
the counts leave out, so a share errs low):

  decode_bytes_weights         everything a step reads WHATEVER the
      routing: each layer's five attention matrices (W_UK and W_UV are
      `kv_b_proj` cut in two, the same bytes) and two inner norms, the
      dense layer's three matrices, each sparse layer's router, its bias
      and the shared expert, and the head's [D, V] matrix. No routed
      expert and not the embedding table.
  decode_bytes_per_live_expert one routed expert's three matrices
      (3 D F) times the sparse layers, multiplied by `experts_touched`
      (the program's counter) by the readers.
  decode_bytes_per_kv_token    the ONE latent row of a cached token in
      every layer: layers x (kv_lora_rank + qk_rope_head_dim) x 2 B =
      5 x 1,152 = 5,760.
  decode_bytes_per_window_slot, decode_bytes_per_state_slot  0: no
      window kind and nothing by the slot.
  latent_flops_per_kv_token    the absorbed read's operations a cached
      token: layers x heads x (row + latent) x 2 = 5 x 64 x (576 + 512)
      x 2 = 5 x 139,264: every head's score over the row's 576 values
      and its value sum over the first 512. 121 operations a byte
      against the chip's 240, so the call is bound by HBM only while the
      MXU runs above half its peak: the readers take the larger limit.
  decode_flops_per_row         2 operations a matmul parameter a row
      passes (attention, the dense MLP, router, shared expert, top_k
      routed experts a sparse layer, the head); the attention over the
      cache is `latent_flops_per_kv_token`'s.

Norms' vectors and the activations are left out: the counts err low.
"""

from __future__ import annotations

import collections
import dataclasses

from harness import configs

BYTES = 2       # bf16

RefConfig = collections.namedtuple(
    "RefConfig", "n_heads nope_dim rope_dim v_head_dim first_k_dense top_k "
    "routed_scale first_expert norm_eps rope_theta yarn_factor yarn_orig "
    "beta_fast beta_slow mscale mscale_all_dim")


def _program():
    from ray_tpu.models import kimi_k2

    return kimi_k2


def model():
    """What harness/families.py asks of a model module (no `loss_fn`:
    the family has no training form). The seeded weights are the
    program's own initialisation (0.02; W_o and every W_down at
    0.02 / sqrt(2 L); norm scales 1; the router's bias at 0.002,
    normal): the head is untied and seeded, so no position predicts its
    own input."""
    return _program()


def _fixed(config: dict) -> dict:
    """What the flags and `rope_scaling` say; refuses what the family
    does not build."""
    fixed = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
             "n_shared_experts": 1, "moe_layer_freq": 1,
             "attention_bias": False, "tie_word_embeddings": False,
             "hidden_act": "silu", "num_nextn_predict_layers": 0}
    off = [k for k, v in fixed.items() if config[k] != v]
    yarn = config["rope_scaling"]
    if off or yarn["type"] != "yarn" or (
            config["num_key_value_heads"] != config["num_attention_heads"]):
        raise SystemExit(
            "the kimi_k2 family builds latent attention under YaRN, a "
            "sigmoid router that chooses by a biased score in one group "
            "and renormalises its gates, one shared expert the width of a "
            f"routed one and an untied head; the file differs in {off}")
    return {
        "n_experts_routed": config["published"]["n_routed_experts"],
        "first_expert": config["deployment_share"]["first_expert"],
        "d_ff_shared": config["n_shared_experts"]
        * config["moe_intermediate_size"],
        "yarn_factor": float(yarn["factor"]),
        "yarn_orig": yarn["original_max_position_embeddings"],
        "beta_fast": float(yarn["beta_fast"]),
        "beta_slow": float(yarn["beta_slow"]),
        "mscale": float(yarn["mscale"]),
        "mscale_all_dim": float(yarn["mscale_all_dim"]),
    }


def program_config(config: dict, **overrides):
    fields = {f.name for f in dataclasses.fields(_program().KimiK2Config)}
    kwargs = {**configs.program_kwargs(config, **overrides),
              **_fixed(config)}
    return _program().KimiK2Config(**{k: v for k, v in kwargs.items()
                                      if k in fields})


def reference_config(config: dict) -> RefConfig:
    d, fix = configs.dims(config), _fixed(config)
    return RefConfig(
        n_heads=d["n_heads"], nope_dim=d["qk_nope_head_dim"],
        rope_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
        first_k_dense=d["first_k_dense"], top_k=d["top_k"],
        routed_scale=float(d["routed_scale"]),
        first_expert=fix["first_expert"], norm_eps=d["norm_eps"],
        rope_theta=float(d["rope_theta"]), yarn_factor=fix["yarn_factor"],
        yarn_orig=fix["yarn_orig"], beta_fast=fix["beta_fast"],
        beta_slow=fix["beta_slow"], mscale=fix["mscale"],
        mscale_all_dim=fix["mscale_all_dim"])


def layer_params(config: dict) -> dict:
    """Parameters by part, norms' vectors included: one layer's attention
    (five matrices, two inner norms), a layer's two norms, the dense MLP,
    a sparse layer's router with its bias, its shared expert, one routed
    expert; and how many layers of each."""
    d, fix = configs.dims(config), _fixed(config)
    D, H = d["d_model"], d["n_heads"]
    Rq, R = d["q_lora_rank"], d["kv_lora_rank"]
    Kn, Kr, Kv = d["qk_nope_head_dim"], d["qk_rope_head_dim"], d["v_head_dim"]
    n_dense = min(d["first_k_dense"], d["n_layers"])
    return {
        "attention": (D * Rq + Rq + Rq * H * (Kn + Kr) + D * (R + Kr) + R
                      + R * H * (Kn + Kv) + H * Kv * D),
        "norms": 2 * D,
        "dense_mlp": 3 * D * d["d_ff_dense"],
        "router": (D + 1) * fix["n_experts_routed"],
        "shared": 3 * D * fix["d_ff_shared"],
        "expert": 3 * D * d["d_ff"],
        "row": R + Kr, "latent": R,
        "n_dense": n_dense, "n_sparse": d["n_layers"] - n_dense,
    }


def parameters(config: dict) -> int:
    """Every parameter the share holds."""
    d, per = configs.dims(config), layer_params(config)
    L = per["n_dense"] + per["n_sparse"]
    return (L * (per["attention"] + per["norms"])
            + per["n_dense"] * per["dense_mlp"]
            + per["n_sparse"] * (per["router"] + per["shared"]
                                 + d["n_experts"] * per["expert"])
            + 2 * d["vocab_size"] * d["d_model"] + d["d_model"])


def serve_consts(config: dict) -> dict:
    d, per = configs.dims(config), layer_params(config)
    L = per["n_dense"] + per["n_sparse"]
    always = (L * per["attention"] + per["n_dense"] * per["dense_mlp"]
              + per["n_sparse"] * (per["router"] + per["shared"])
              + d["d_model"] * d["vocab_size"])
    return {
        "decode_bytes_weights": BYTES * always,
        "decode_bytes_per_live_expert": BYTES * per["n_sparse"]
        * per["expert"],
        "decode_bytes_per_kv_token": BYTES * L * per["row"],
        # `decode_stream_roofline` sums five byte terms and reads nothing
        # where one is missing: no window kind, nothing by the slot.
        "decode_bytes_per_window_slot": 0.0,
        "decode_bytes_per_state_slot": 0.0,
        "latent_flops_per_kv_token": 2.0 * L * d["n_heads"]
        * (per["row"] + per["latent"]),
        "decode_flops_per_row": 2.0 * (
            always + per["n_sparse"] * d["top_k"] * per["expert"]),
    }


def train_consts(config: dict, seq: int) -> dict:
    """Operations forward and backward REQUIRE per token (6 per matmul
    parameter a token passes: top_k routed experts a sparse layer, the
    head once) plus the causal score/value term in the plain form (a
    head's 192-wide score and 128-wide value). No training cell runs
    this family; the count is here because a family has five
    functions."""
    d, per = configs.dims(config), layer_params(config)
    L = per["n_dense"] + per["n_sparse"]
    active = (L * per["attention"] + per["n_dense"] * per["dense_mlp"]
              + per["n_sparse"] * (per["router"] + per["shared"]
                                   + d["top_k"] * per["expert"]))
    attn = 6 * (d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
                + d["v_head_dim"]) * d["n_heads"] * L * seq
    return {"train_flops_per_token":
            6.0 * (active + d["d_model"] * d["vocab_size"]) + attn}
