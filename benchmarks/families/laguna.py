"""The `laguna` family: Laguna-S-2.1's block (`ray_tpu.models.laguna`:
window layers beside full ones over two kinds of K/V, unequal query head
counts over one set of KV heads, a per-head output gate, YaRN on half a
head in full layers, a sigmoid top-k router over more experts than the
chip holds, one shared expert), held to harness/reference/laguna_ref.py.
What a family is, and what each function is for: harness/families.py.

The configuration file holds ONE CHIP'S SHARE of a two-chip
expert-parallel deployment under the keys of the public config.json:
`num_experts` is the experts held (the router's width is the published
count, `published.num_experts`), `vocab_size` the rows of embedding and
head held, `num_hidden_layers` the leading layers run. `layer_types`,
`mlp_layer_types` and `num_attention_heads_per_layer` are copied whole
and their first `num_hidden_layers` entries are run.

The bytes a decode step must read are counted here, from the
configuration's own sizes (bf16, 2 B a parameter):

  decode_bytes_weights         everything a step reads WHATEVER the
      routing: each layer's W_q, W_k, W_v, W_g, W_o at its own head
      count, the dense layers' three matrices, each sparse layer's
      router and shared expert, and the head's [D, V] matrix. No routed
      expert and not the embedding table (a step reads 64 rows of it).
  decode_bytes_per_live_expert one routed expert's three matrices
      (3 D F) times the sparse layers: multiplied by the MEAN number of
      held experts that had a row in a layer of a step
      (`experts_touched`, the program's counter), so that a
      roofline share counts only experts a token reached and errs low
      (the layer streams all it holds).
  decode_bytes_per_kv_token    K and V of one cached token in the FULL
      layers: full layers x 2 x KV heads x head size.
  decode_bytes_per_window_slot K and V of one decoding slot's window in
      the WINDOW layers: window layers x window x 2 x KV heads x head
      size; a slot whose context is shorter than the window reads less,
      and no context of the cell's traffic is.

Norms, the ring's row ids and the activations are left out: the count
errs low.
"""

from __future__ import annotations

import collections
import dataclasses

from harness import configs

BYTES = 2       # bf16

RefConfig = collections.namedtuple(
    "RefConfig", "layer_types dense_layers heads_full heads_window "
    "n_kv_heads window top_k routed_scale first_expert norm_eps "
    "theta_window theta_full rotary_dim yarn_factor yarn_orig beta_fast "
    "beta_slow attention_factor")

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


def _program():
    from ray_tpu.models import laguna

    return laguna


def model():
    """What harness/families.py asks of a model module (no `loss_fn`:
    the family has no training form). The seeded weights are the
    program's own initialisation (0.02; W_o and every W_down at
    0.02 / sqrt(2 L); norm scales 1): the head is untied and seeded, so
    no position predicts its own input."""
    return _program()


def _layers(config: dict) -> dict:
    """What the per-layer lists and the rope groups say of the layers
    that are run."""
    L = config["num_hidden_layers"]
    kinds = tuple(_KINDS[t] for t in config["layer_types"][:L])
    heads = config["num_attention_heads_per_layer"][:L]
    per_kind = {k: {h for h, t in zip(heads, kinds) if t == k}
                for k in ("full", "window")}
    if (per_kind["full"] != {config["num_attention_heads"]}
            or len(per_kind["window"]) != 1):
        raise SystemExit("the laguna family wants one head count a layer "
                         f"kind, got {per_kind}")
    full = config["rope_parameters"]["full_attention"]
    window = config["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], window["rope_type"],
            window["partial_rotary_factor"]) != ("yarn", "default", 1):
        raise SystemExit("the laguna family builds YaRN on full layers and "
                         "plain whole-head rope on window layers")
    return {
        "layer_types": kinds,
        "dense_layers": tuple(
            l for l, t in enumerate(config["mlp_layer_types"][:L])
            if t == "dense"),
        "n_heads_window": per_kind["window"].pop(),
        "n_experts_routed": config["published"]["num_experts"],
        "first_expert": config["deployment_share"]["first_expert"],
        "rope_theta": float(full["rope_theta"]),
        "rotary_dim": int(full["partial_rotary_factor"] * config["head_dim"]),
        "yarn_factor": float(full["factor"]),
        "yarn_orig": int(full["original_max_position_embeddings"]),
        "beta_fast": float(full["beta_fast"]),
        "beta_slow": float(full["beta_slow"]),
        "attention_factor": float(full["attention_factor"]),
        "rope_theta_window": float(window["rope_theta"]),
    }


def program_config(config: dict, **overrides):
    fields = {f.name for f in dataclasses.fields(_program().LagunaConfig)}
    kwargs = {**configs.program_kwargs(config, **overrides),
              **_layers(config)}
    return _program().LagunaConfig(**{k: v for k, v in kwargs.items()
                                      if k in fields})


def reference_config(config: dict) -> RefConfig:
    d, lay = configs.dims(config), _layers(config)
    return RefConfig(
        layer_types=lay["layer_types"], dense_layers=lay["dense_layers"],
        heads_full=d["n_heads"], heads_window=lay["n_heads_window"],
        n_kv_heads=d["n_kv_heads"], window=d["window"], top_k=d["top_k"],
        routed_scale=d["routed_scale"], first_expert=lay["first_expert"],
        norm_eps=d["norm_eps"], theta_window=lay["rope_theta_window"],
        theta_full=lay["rope_theta"], rotary_dim=lay["rotary_dim"],
        yarn_factor=lay["yarn_factor"], yarn_orig=lay["yarn_orig"],
        beta_fast=lay["beta_fast"], beta_slow=lay["beta_slow"],
        attention_factor=lay["attention_factor"])


def layer_params(config: dict) -> dict:
    """Matmul parameters by part: one layer's attention of each kind, a
    dense MLP, a sparse layer's router and shared expert, one routed
    expert; and how many layers of each."""
    d, lay = configs.dims(config), _layers(config)
    D, K, G = d["d_model"], d["head_dim"], d["n_kv_heads"]
    attn = lambda H: D * H * K + 2 * D * G * K + H * K * D + D * H
    kinds = lay["layer_types"]
    n_dense = len(lay["dense_layers"])
    return {
        "attention_full": attn(d["n_heads"]),
        "attention_window": attn(lay["n_heads_window"]),
        "dense_mlp": 3 * D * d["d_ff_dense"],
        "router": D * lay["n_experts_routed"],
        "shared": 3 * D * d["d_ff_shared"],
        "expert": 3 * D * d["d_ff"],
        "n_full": kinds.count("full"), "n_window": kinds.count("window"),
        "n_dense": n_dense, "n_sparse": len(kinds) - n_dense,
    }


def serve_consts(config: dict) -> dict:
    d = configs.dims(config)
    per = layer_params(config)
    kv_token = BYTES * 2 * d["n_kv_heads"] * d["head_dim"]
    return {
        "decode_bytes_weights": BYTES * (
            per["n_full"] * per["attention_full"]
            + per["n_window"] * per["attention_window"]
            + per["n_dense"] * per["dense_mlp"]
            + per["n_sparse"] * (per["router"] + per["shared"])
            + d["d_model"] * d["vocab_size"]),
        "decode_bytes_per_live_expert": BYTES * per["n_sparse"] * per["expert"],
        "decode_bytes_per_kv_token": per["n_full"] * kv_token,
        "decode_bytes_per_window_slot":
            per["n_window"] * d["window"] * kv_token,
        # `decode_stream_roofline` sums five byte terms and reads nothing
        # where one is missing: this family has no recurrent state.
        "decode_bytes_per_state_slot": 0.0,
    }


def train_consts(config: dict, seq: int) -> dict:
    """Operations forward and backward REQUIRE per token (6 per matmul
    parameter a token passes: top_k routed experts and the shared one a
    sparse layer, the head once) plus the causal score/value term (a
    window layer's keys are at most the window). No training cell runs
    this family; the count is here because a family has five
    functions."""
    d = configs.dims(config)
    per, lay = layer_params(config), _layers(config)
    active = (per["n_full"] * per["attention_full"]
              + per["n_window"] * per["attention_window"]
              + per["n_dense"] * per["dense_mlp"]
              + per["n_sparse"] * (per["router"] + per["shared"]
                                   + d["top_k"] * per["expert"]))
    attn = 12 * d["head_dim"] * (
        per["n_full"] * d["n_heads"] * seq
        + per["n_window"] * lay["n_heads_window"] * min(seq, d["window"]))
    return {"train_flops_per_token":
            6.0 * (active + d["d_model"] * d["vocab_size"]) + attn}
