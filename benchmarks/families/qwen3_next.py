"""The `qwen3_next` family: Qwen3-Next-80B-A3B-Instruct's block
(`ray_tpu.models.qwen3_next`: three Gated DeltaNet layers, whose
recurrent state lives by the slot beside the pages, to one gated
softmax-attention layer at head size 256; a softmax top-k router over
more experts than the chip holds; a gated shared expert), held to
harness/reference/qwen3_next_ref.py. What a family is, and what each
function is for: harness/families.py.

The configuration file holds ONE CHIP'S SHARE of a four-chip
expert-parallel group under the keys of the public config.json:
`num_experts` is the experts held (the router's width is the published
count, `published.num_experts`), `vocab_size` the rows of embedding and
head held, `num_hidden_layers` the leading layers run.

The bytes and operations counted here, from the configuration's own
sizes (bf16 weights and pages, 2 B; the recurrent state float32, 4 B):

  decode_bytes_weights          everything a step reads WHATEVER the
      routing: each linear layer's W_qkvz, W_ba, convolution and W_out,
      each full layer's W_q (query and gate), W_k, W_v, W_o, every
      layer's router, shared expert and its gate, and the head's [D, V]
      matrix. No routed expert and not the embedding table.
  decode_bytes_per_live_expert  one routed expert's three matrices
      (3 D F) times the layers: multiplied by the MEAN number of held
      experts that had a row in a layer of a step
      (`experts_touched`), so a roofline share errs low.
  decode_bytes_per_kv_token     K and V of one cached token in the full
      layers: full layers x 2 x KV heads x head size.
  decode_bytes_per_state_slot   one decoding slot's recurrent state and
      convolution tail in the linear layers, READ AND WRITTEN: linear
      layers x (Hv dk dv x 4 B + (taps - 1) x channels x 2 B) x 2.
  chunk_scan_bytes_per_token    what the chunked scan must move a prompt
      token and linear layer, summed over the linear layers: q, k, v in
      and o out at float32 a value head, and a 128-token row's state
      read and written, spread over its tokens.
  chunk_scan_flops_per_token    the scan's matmuls a token (blocks of 64
      tokens a head: k k^T and q k^T, the 11 matmuls of the triangular
      inverse, U and W, and the state pass's four), summed over the
      linear layers; counted once, whatever passes the precision costs.

Norms and activations are left out: the counts err low.
"""

from __future__ import annotations

import collections
import dataclasses
import types

from harness import configs

BYTES = 2       # bf16
STATE_BYTES = 4     # the recurrent state is float32
SCAN_BLOCK = 64     # tokens a block of the program's chunked scan

RefConfig = collections.namedtuple(
    "RefConfig", "n_layers full_interval n_heads n_kv_heads lin_k_heads "
    "lin_v_heads top_k first_expert norm_eps rope_theta rotary_dim")


def _program():
    from ray_tpu.models import qwen3_next

    return qwen3_next


def _alive(specs: dict) -> dict:
    """The program's `param_specs` with the two leaves that set the
    linear layers' decay as the benchmark seeds them, for the check's
    sake and for no other: `g_dt_bias` ~ N(0, 4) and `g_A_log` ~ N(0, 1)
    a value head. harness/weights.py fills a leaf from its spec alone
    (a zero-mean normal, ones or zeros); under the model's own start
    (`dt_bias` ones, A ~ U(0, 16)) every head reads g ~ -10 a token and
    under N(0, 0.02) leaves g ~ -0.7: the state is forgotten within one
    to three tokens and no fault in carrying it could show.
    softplus(N(0, 4)) spreads the heads' time constants from under one
    token to hundreds (a quarter of the heads remember more than 15
    tokens, a tenth more than 150): the spread a trained model has, and
    what the state is for."""
    wide = lambda name, scale: {"shape": specs[name]["shape"],
                                "init": "normal", "scale": scale}
    return {**specs, "g_dt_bias": wide("g_dt_bias", 4.0),
            "g_A_log": wide("g_A_log", 1.0)}


def model():
    """What harness/families.py asks of a model module (no `loss_fn`:
    the family has no training form), with the benchmark's
    `param_specs`."""
    qn = _program()
    return types.SimpleNamespace(
        param_specs=lambda cfg: _alive(qn.param_specs(cfg)),
        partition_rules=qn.partition_rules, init_params=qn.init_params)


def _derived(config: dict) -> dict:
    if config.get("rope_scaling") is not None:
        raise SystemExit("the qwen3_next family builds plain rope")
    return {"rotary_dim": int(config["partial_rotary_factor"]
                              * config["head_dim"]),
            "n_experts_routed": config["published"]["num_experts"],
            "first_expert": config["deployment_share"]["first_expert"]}


def program_config(config: dict, **overrides):
    fields = {f.name for f in dataclasses.fields(_program().Qwen3NextConfig)}
    kwargs = {**configs.program_kwargs(config, **overrides),
              **_derived(config)}
    return _program().Qwen3NextConfig(**{k: v for k, v in kwargs.items()
                                         if k in fields})


def reference_config(config: dict) -> RefConfig:
    d, more = configs.dims(config), _derived(config)
    return RefConfig(
        n_layers=d["n_layers"], full_interval=d["full_interval"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        lin_k_heads=d["lin_k_heads"], lin_v_heads=d["lin_v_heads"],
        top_k=d["top_k"], first_expert=more["first_expert"],
        norm_eps=d["norm_eps"], rope_theta=float(d["rope_theta"]),
        rotary_dim=more["rotary_dim"])


def layer_params(config: dict) -> dict:
    """Matmul parameters by part: one linear layer's and one full
    layer's mixer, a layer's router and shared expert (with its gate),
    one routed expert; how many layers of each kind; and a linear
    layer's state, in elements."""
    d = configs.dims(config)
    D, H, G, K = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    Hk, Hv, dk, dv = (d["lin_k_heads"], d["lin_v_heads"], d["lin_k_dim"],
                      d["lin_v_dim"])
    channels = 2 * Hk * dk + Hv * dv
    n_full = d["n_layers"] // d["full_interval"]
    return {
        "linear": (D * (2 * Hk * dk + 2 * Hv * dv) + D * 2 * Hv
                   + d["conv_taps"] * channels + Hv * dv * D),
        "full": D * 2 * H * K + 2 * D * G * K + H * K * D,
        "router": D * config["published"]["num_experts"],
        "shared": 3 * D * d["d_ff_shared"] + D,
        "expert": 3 * D * d["d_ff"],
        "n_full": n_full, "n_linear": d["n_layers"] - n_full,
        "state": Hv * dk * dv, "tail": (d["conv_taps"] - 1) * channels,
    }


def serve_consts(config: dict) -> dict:
    d, per = configs.dims(config), layer_params(config)
    Hv, dk, dv = d["lin_v_heads"], d["lin_k_dim"], d["lin_v_dim"]
    T = SCAN_BLOCK
    block_flops = 2 * (
        2 * T * T * dk                       # k k^T, q k^T
        + 11 * T * T * T                     # the triangular inverse
        + T * T * dv + T * T * dk            # U, W
        + 2 * T * dk * dv + T * T * dv + T * dk * dv)    # the state pass
    return {
        "decode_bytes_weights": BYTES * (
            per["n_linear"] * per["linear"] + per["n_full"] * per["full"]
            + d["n_layers"] * (per["router"] + per["shared"])
            + d["d_model"] * d["vocab_size"]),
        "decode_bytes_per_live_expert":
            BYTES * d["n_layers"] * per["expert"],
        "decode_bytes_per_kv_token":
            per["n_full"] * BYTES * 2 * d["n_kv_heads"] * d["head_dim"],
        # `decode_stream_roofline` sums five byte terms and reads nothing
        # where one is missing: this family has no window layer (its two
        # attention layers read every cached token).
        "decode_bytes_per_window_slot": 0.0,
        "decode_bytes_per_state_slot": per["n_linear"] * 2 * (
            STATE_BYTES * per["state"] + BYTES * per["tail"]),
        "chunk_scan_bytes_per_token": per["n_linear"] * (
            4 * Hv * (2 * dk + 2 * dv)
            + 2 * STATE_BYTES * per["state"]
            // config["serve"]["prefill_chunk"]),
        "chunk_scan_flops_per_token":
            per["n_linear"] * Hv * block_flops // T,
    }


def train_consts(config: dict, seq: int) -> dict:
    """Operations forward and backward REQUIRE per token (6 per matmul
    parameter a token passes: top_k routed experts and the shared one a
    layer, the head once) plus the full layers' causal score/value term
    and the linear layers' state update (4 dk dv a value head). No
    training cell runs this family; the count is here because a family
    has five functions."""
    d, per = configs.dims(config), layer_params(config)
    active = (per["n_linear"] * per["linear"] + per["n_full"] * per["full"]
              + d["n_layers"] * (per["router"] + per["shared"]
                                 + d["top_k"] * per["expert"]))
    mix = (12 * d["head_dim"] * per["n_full"] * d["n_heads"] * seq
           + 3 * 4 * per["n_linear"] * per["state"])
    return {"train_flops_per_token":
            6.0 * (active + d["d_model"] * d["vocab_size"]) + mix}
