"""The `mimo_v2` family: MiMo-V2-Flash's block (`ray_tpu.models.mimo_v2`:
K heads of 192 beside V heads of 128, a learned sink in the window
layers' softmax, 4 and 8 KV heads by layer kind under 64 query heads,
rotary on a third of a head at two thetas, V scaled by 0.707, a sigmoid
router that chooses by a biased score and gates by the unbiased one over
more experts than the chip holds, no shared expert), held to
harness/reference/mimo_v2_ref.py. What a family is, and what each
function is for: harness/families.py.

The configuration file holds ONE CHIP'S SHARE of a sixteen-chip
expert-parallel group under the keys of the public config.json:
`n_routed_experts` is the experts held (the router's width is the
published count, `published.n_routed_experts`), `vocab_size` the rows of
embedding and head held, `num_hidden_layers` the leading layers run.
`hybrid_layer_pattern` (0 full, 1 window) and `moe_layer_freq` (0 dense,
1 sparse) are copied whole and their first `num_hidden_layers` entries
are run.

The bytes a decode step must read are counted here, from the
configuration's own sizes at the PUBLISHED head sizes (bf16, 2 B a
parameter; a pool that padded a head would still be counted at 192 and
128):

  decode_bytes_weights         everything a step reads WHATEVER the
      routing: each layer's W_q, W_k, W_v, W_o at its kind's KV head
      count and the window layers' sinks, the dense layer's three
      matrices, each sparse layer's router and its bias, and the head's
      [D, V] matrix. No routed expert and not the embedding table.
  decode_bytes_per_live_expert one routed expert's three matrices
      (3 D F) times the sparse layers: multiplied by the MEAN number of
      held experts that had a row in a layer of a step
      (`experts_touched`, the program's counter), so that a
      roofline share counts only experts a token reached and errs low
      (the layer streams all it holds).
  decode_bytes_per_kv_token    K and V of one cached token in the FULL
      layers: full layers x KV heads x (K head size + V head size).
  decode_bytes_per_window_slot K and V of one decoding slot's window in
      the WINDOW layers: window layers x window x KV heads x (K + V head
      size); 128 keys, where the ring's pages hold up to 192, so a share
      errs low.

Norms, the ring's row ids and the activations are left out: the count
errs low.
"""

from __future__ import annotations

import collections
import dataclasses

from harness import configs

BYTES = 2       # bf16

RefConfig = collections.namedtuple(
    "RefConfig", "layer_types dense_layers n_heads kv_heads_full "
    "kv_heads_window head_dim v_head_dim value_scale window sink_kinds "
    "top_k first_expert norm_eps theta_full theta_window rotary_dim")

_KINDS = {0: "full", 1: "window"}


def _program():
    from ray_tpu.models import mimo_v2

    return mimo_v2


def model():
    """What harness/families.py asks of a model module (no `loss_fn`:
    the family has no training form). The seeded weights are the
    program's own initialisation (0.02; W_o and every W_down at
    0.02 / sqrt(2 L); norm scales 1; sinks at 1.0 and the router's bias
    at 0.002, normal): the head is untied and seeded, so no position
    predicts its own input."""
    return _program()


def _layers(config: dict) -> dict:
    """What the per-layer lists and the flags say of the layers that are
    run; refuses what the family does not build."""
    L = config["num_hidden_layers"]
    same = {"swa_head_dim": "head_dim", "swa_v_head_dim": "v_head_dim",
            "swa_num_attention_heads": "num_attention_heads",
            "sliding_window_size": "sliding_window"}
    fixed = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
             "n_shared_experts": None, "attention_bias": False,
             "tie_word_embeddings": False, "hidden_act": "silu"}
    off = ([k for k, v in same.items() if config[k] != config[v]]
           + [k for k, v in fixed.items() if config[k] != v])
    if off or config["routed_scaling_factor"] not in (None, 1, 1.0):
        raise SystemExit(
            "the mimo_v2 family builds one head size and query head count "
            "for both layer kinds, a sigmoid router that chooses by a "
            "biased score in one group and renormalises its gates, no "
            f"shared expert and no gate scale; the file differs in {off}")
    return {
        "layer_types": tuple(_KINDS[t]
                             for t in config["hybrid_layer_pattern"][:L]),
        "dense_layers": tuple(
            l for l, t in enumerate(config["moe_layer_freq"][:L]) if t == 0),
        "sink_kinds": tuple(
            kind for kind, key in (
                ("full", "add_full_attention_sink_bias"),
                ("window", "add_swa_attention_sink_bias")) if config[key]),
        "n_experts_routed": config["published"]["n_routed_experts"],
        "first_expert": config["deployment_share"]["first_expert"],
        "rotary_dim": int(config["partial_rotary_factor"]
                          * config["head_dim"]),
    }


def program_config(config: dict, **overrides):
    fields = {f.name for f in dataclasses.fields(_program().MiMoV2Config)}
    kwargs = {**configs.program_kwargs(config, **overrides),
              **_layers(config)}
    return _program().MiMoV2Config(**{k: v for k, v in kwargs.items()
                                      if k in fields})


def reference_config(config: dict) -> RefConfig:
    d, lay = configs.dims(config), _layers(config)
    return RefConfig(
        layer_types=lay["layer_types"], dense_layers=lay["dense_layers"],
        n_heads=d["n_heads"], kv_heads_full=d["n_kv_heads"],
        kv_heads_window=d["n_kv_heads_window"], head_dim=d["head_dim"],
        v_head_dim=d["v_head_dim"], value_scale=d["value_scale"],
        window=d["window"], sink_kinds=lay["sink_kinds"], top_k=d["top_k"],
        first_expert=lay["first_expert"], norm_eps=d["norm_eps"],
        theta_full=float(d["rope_theta"]),
        theta_window=float(d["rope_theta_window"]),
        rotary_dim=lay["rotary_dim"])


def layer_params(config: dict) -> dict:
    """Matmul parameters by part: one layer's attention of each kind
    (a window layer's with its sinks), the dense MLP, a sparse layer's
    router with its bias, one routed expert; and how many layers of
    each."""
    d, lay = configs.dims(config), _layers(config)
    D, H, Kq, Kv = d["d_model"], d["n_heads"], d["head_dim"], d["v_head_dim"]
    attn = lambda G, kind: (D * (H * Kq + G * Kq + G * Kv) + H * Kv * D
                            + (H if kind in lay["sink_kinds"] else 0))
    kinds = lay["layer_types"]
    n_dense = len(lay["dense_layers"])
    return {
        "attention_full": attn(d["n_kv_heads"], "full"),
        "attention_window": attn(d["n_kv_heads_window"], "window"),
        "dense_mlp": 3 * D * d["d_ff_dense"],
        "router": (D + 1) * lay["n_experts_routed"],
        "expert": 3 * D * d["d_ff"],
        "n_full": kinds.count("full"), "n_window": kinds.count("window"),
        "n_dense": n_dense, "n_sparse": len(kinds) - n_dense,
    }


def serve_consts(config: dict) -> dict:
    d = configs.dims(config)
    per = layer_params(config)
    token = lambda G: BYTES * G * (d["head_dim"] + d["v_head_dim"])
    return {
        "decode_bytes_weights": BYTES * (
            per["n_full"] * per["attention_full"]
            + per["n_window"] * per["attention_window"]
            + per["n_dense"] * per["dense_mlp"]
            + per["n_sparse"] * per["router"]
            + d["d_model"] * d["vocab_size"]),
        "decode_bytes_per_live_expert": BYTES * per["n_sparse"] * per["expert"],
        "decode_bytes_per_kv_token": per["n_full"] * token(d["n_kv_heads"]),
        "decode_bytes_per_window_slot":
            per["n_window"] * d["window"] * token(d["n_kv_heads_window"]),
        # `decode_stream_roofline` sums five byte terms and reads nothing
        # where one is missing: this family has no recurrent state.
        "decode_bytes_per_state_slot": 0.0,
    }


def train_consts(config: dict, seq: int) -> dict:
    """Operations forward and backward REQUIRE per token (6 per matmul
    parameter a token passes: top_k routed experts a sparse layer, the
    head once) plus the causal score/value term (scores contract the K
    head size, values the V head size; a window layer's keys are at most
    the window). No training cell runs this family; the count is here
    because a family has five functions."""
    d = configs.dims(config)
    per = layer_params(config)
    active = (per["n_full"] * per["attention_full"]
              + per["n_window"] * per["attention_window"]
              + per["n_dense"] * per["dense_mlp"]
              + per["n_sparse"] * (per["router"]
                                   + d["top_k"] * per["expert"]))
    attn = 6 * (d["head_dim"] + d["v_head_dim"]) * d["n_heads"] * (
        per["n_full"] * seq + per["n_window"] * min(seq, d["window"]))
    return {"train_flops_per_token":
            6.0 * (active + d["d_model"] * d["vocab_size"]) + attn}
