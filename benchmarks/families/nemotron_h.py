"""The `nemotron_h` family: Nemotron-3-Super-120B-A12B's block
(`ray_tpu.models.nemotron_h`: layers of ONE sublayer each by a pattern
string; Mamba-2 mixers whose float32 matrix state of 64 x 128 a head
lives by the slot beside the pages; a grouped-query attention of 32
heads over 2 KV heads without positions; LATENT expert layers, 22 of 512
two-matrix squared-ReLU experts in a latent of 1,024 under a sigmoid
router that chooses by a biased score and gates by 5 x the unbiased one,
beside a shared expert on the model's width; an untied head), held to
harness/reference/nemotron_h_ref.py. What a family is, and what each
function is for: harness/families.py.

The configuration file holds ONE CHIP'S SHARE of a four-chip
expert-parallel group under the keys of the public config.json:
`n_routed_experts` is the experts held (the router's width is the
published count, `published.n_routed_experts`), `vocab_size` the rows of
embedding and head held, `num_hidden_layers` the leading layers run: the
first that many characters of `hybrid_override_pattern`, which the file
keeps whole.

What a decode step must read and do is counted here, from the
configuration's own sizes (bf16 weights, pages and convolution tails,
2 B; the state-space state float32, 4 B):

  decode_bytes_weights         everything a step reads WHATEVER the
      routing: each Mamba-2 layer's W_in and W_out, the attention
      layer's four matrices, each expert layer's router, the two latent
      projections and the shared expert, and the head's [D, V] matrix.
      No routed expert, not the embedding table, not the vectors.
  decode_bytes_per_live_expert one routed expert's TWO matrices
      (2 Dl F) times the expert layers, multiplied by `experts_touched`
      (the program's counter) by the readers.
  decode_bytes_per_kv_token    K and V of one cached token in the
      attention layers: layers x 2 x KV heads x head size x 2 B = 1,024.
  decode_bytes_per_state_slot  one decoding slot's state and convolution
      tail in the Mamba-2 layers, READ AND WRITTEN: layers x (Hm P N x
      4 B + (taps - 1) x channels x 2 B) x 2 = 42.6 MB at five layers.
  ssm_step_bytes_per_slot      the state alone, read and written: what
      the decode step's scan moves (`ssm_step_roofline`; the tail moves
      in `ssm.in`): layers x Hm P N x 4 B x 2 = 41.9 MB.
  decode_bytes_per_window_slot 0.0: no window layers (stated, so that a
      reader that sums a family's byte terms finds every one).
  decode_flops_per_row         2 operations a matmul parameter a row
      passes ON THIS CHIP: the always-read matrices and, an expert
      layer, top_k x held / routed experts (5.5 of a row's 22 choices
      land on the 128 held of 512). The recurrence's own multiplies and
      adds run on the vector units and are left out, as is attention's
      score and value work.
  chunk_scan_bytes_per_token   what a chunk program's scan must move a
      prompt token, summed over the Mamba-2 layers: x in and y out at
      float32 a head value, dt, B and C in, and a 128-token row's state
      read and written, spread over its tokens.
  chunk_scan_flops_per_token   the matmul form's operations a token at
      blocks of `chunk_size` T tokens, summed over the Mamba-2 layers:
      C B^T a group (2 T N), the block's own tokens' (C B^T * L) X a
      head (2 T P), the carried state's read C S (2 N P) and its update
      B^T X (2 N P) a head; counted once and whole (the causal half of
      the two T x T products is what a better kernel could skip),
      whatever passes the float32 precision costs.

Norms and activations are left out: the counts err low.
"""

from __future__ import annotations

import collections
import dataclasses
import types

from harness import configs

BYTES = 2           # bf16
STATE_BYTES = 4     # the state-space state is float32

RefConfig = collections.namedtuple(
    "RefConfig", "pattern m_heads m_groups n_heads n_kv_heads top_k "
    "routed_scale first_expert norm_eps")


def _program():
    from ray_tpu.models import nemotron_h

    return nemotron_h


def _alive(specs: dict) -> dict:
    """The program's `param_specs` with four stacks as the benchmark
    seeds them, for the check's sake and for no other: the two that set
    the decay, and the two output projections of an expert layer.
    harness/weights.py fills a leaf from its spec alone (a zero-mean
    normal, ones or zeros; anything else is zeros), and under
    the model's own start (a rate of 1-16, steps of 1e-3 to 1e-1) a
    filler that knows no such init leaves `m_dt_b` and `m_A_log` at
    ZERO: a step of softplus(W_in u) ~ 0.7 and a rate of 1 in every
    head, every state forgetting at the one rate of e^-0.7 a token, and
    no fault in carrying the state past a few tokens could show.

      m_dt_b ~ N(0, 4), m_A_log ~ N(0, 1) a head, families/jamba.py's
          own and by its argument: with W_in u ~ N(0, 1.3) under it,
          softplus(N(0, 4)) times exp(N(0, 1)) spreads the heads' time
          constants 1 / (dt exp(A_log)) from under one token to
          thousands (jamba's 2 M draws: 42 % forget within a token, 27 %
          remember more than 15 tokens, 12 % more than 150, 4 % more
          than 1,500): the state lives for hundreds of tokens in a
          tenth of the 640 (layer, head) pairs.

      s_down at a QUARTER of the residual scale (0.02 / sqrt(L) / 4) and
          lat_out at 0.02, not the residual scale. A squared ReLU is
          never negative, so under zero-mean random weights a relu^2
          MLP's output has a CONSTANT part, W_2^T E[relu(h)^2], the same
          vector whatever the token (0.45 of the varying part's size,
          whatever the widths); the shared expert reads every token, so
          with the program's own scales its constant part is the largest
          thing each expert layer adds and it compounds: measured at the
          published widths on 256 random tokens (my chip run, PR 62),
          the residual stream's mean pairwise cosine rose 0.05, 0.28,
          0.48, 0.61, 0.73, 0.79 through the five expert layers, 47 of
          256 positions had distinct best tokens, the last layer's
          router reached 54 of the 128 held experts, and under greedy
          decoding every slot fell into one attractor (`experts_touched`
          11.8 of 128, top-1 agreement 0.9997: a check that could refuse
          nothing). The ROUTED experts' constant parts differ by expert
          and average out over a row's 22. With these two scales the
          same reading is 0.38, 195 of 256 and 108-128 of 128, and the
          routed sum (the thing this model adds) is as large in the
          stream as the shared expert. A trained model has whatever
          mean training left; the seed is chosen so that the check can
          see anything (families/zaya.py's `_check_visible` argument).

    The router's correction bias is the program's own seed (normal at
    0.002, families/kimi_k2.py's argument: it moves a few percent of the
    choices, `router_bias_moved` reports how many); the head is untied
    and seeded, so no position predicts its own input."""
    wide = lambda name, scale: {"shape": specs[name]["shape"],
                                "init": "normal", "scale": scale}
    return {**specs, "m_dt_b": wide("m_dt_b", 4.0),
            "m_A_log": wide("m_A_log", 1.0),
            "s_down": wide("s_down", specs["s_down"]["scale"] / 4),
            "lat_out": wide("lat_out", 0.02)}


def model():
    """What harness/families.py asks of a model module (no `loss_fn`:
    the family has no training form), with the benchmark's
    `param_specs`."""
    nh = _program()
    return types.SimpleNamespace(
        param_specs=lambda cfg: _alive(nh.param_specs(cfg)),
        partition_rules=nh.partition_rules, init_params=nh.init_params)


def _fixed(config: dict) -> dict:
    """What the flags say; refuses what the family does not build."""
    built = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
             "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
             "n_shared_experts": 1, "use_conv_bias": True,
             "mamba_proj_bias": False, "mlp_bias": False,
             "attention_bias": False, "use_bias": False,
             "tie_word_embeddings": False, "sliding_window": None,
             "moe_shared_expert_overlap": False}
    wrong = {k: config.get(k) for k, v in built.items()
             if config.get(k) != v}
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    if (wrong or set(pattern) - set("ME*")
            or config["expand"] * config["hidden_size"]
            != config["mamba_num_heads"] * config["mamba_head_dim"]
            or config["norm_eps"] != config["layer_norm_epsilon"]):
        raise SystemExit(
            f"the nemotron_h family builds {built}, a pattern of M, E and "
            "*, and Mamba-2 heads that make up expand x hidden_size; the "
            f"configuration says {wrong}, {pattern!r}")
    return {
        "pattern": pattern,
        "n_experts_routed": config["published"]["n_routed_experts"],
        "first_expert": config["deployment_share"]["first_expert"],
    }


def program_config(config: dict, **overrides):
    fields = {f.name for f in dataclasses.fields(_program().NemotronHConfig)}
    kwargs = {**configs.program_kwargs(config, **overrides),
              **_fixed(config)}
    return _program().NemotronHConfig(**{k: v for k, v in kwargs.items()
                                         if k in fields})


def reference_config(config: dict) -> RefConfig:
    d, fix = configs.dims(config), _fixed(config)
    return RefConfig(
        pattern=fix["pattern"], m_heads=d["m_heads"], m_groups=d["m_groups"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"], top_k=d["top_k"],
        routed_scale=float(d["routed_scale"]),
        first_expert=fix["first_expert"], norm_eps=d["norm_eps"])


def layer_params(config: dict) -> dict:
    """Matmul parameters by part: one Mamba-2 layer's and the attention
    layer's mixer, an expert layer's router, latent projections, shared
    expert and ONE routed expert, the head; how many layers of each
    kind; and a Mamba-2 layer's state and tail, in elements."""
    d, fix = configs.dims(config), _fixed(config)
    D, H, G, K = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    Hm, P, Gm, N = d["m_heads"], d["m_head_dim"], d["m_groups"], d["d_state"]
    Dn, channels = Hm * P, Hm * P + 2 * Gm * N
    Dl, F, Fs = d["d_latent"], d["d_ff"], d["d_ff_shared"]
    count = fix["pattern"].count
    return {
        "mamba": D * (Dn + channels + Hm) + Dn * D,
        "attn": D * (H + 2 * G) * K + H * K * D,
        "router": D * fix["n_experts_routed"],
        "latent": 2 * D * Dl, "shared": 2 * D * Fs, "expert": 2 * Dl * F,
        "head": D * d["vocab_size"],
        "n_mamba": count("M"), "n_attn": count("*"), "n_expert": count("E"),
        "state": Hm * P * N, "tail": (d["d_conv"] - 1) * channels,
    }


def parameters(config: dict) -> int:
    """Every matmul parameter the share holds, the embedding included."""
    d, per = configs.dims(config), layer_params(config)
    return (_always(per) + per["head"]
            + per["n_expert"] * d["n_experts"] * per["expert"])


def _always(per: dict) -> int:
    """The matrices a decode step reads whatever the routing."""
    return (per["n_mamba"] * per["mamba"] + per["n_attn"] * per["attn"]
            + per["n_expert"] * (per["router"] + per["latent"]
                                 + per["shared"]) + per["head"])


def serve_consts(config: dict) -> dict:
    d, per = configs.dims(config), layer_params(config)
    fix = _fixed(config)
    Hm, P, Gm, N = d["m_heads"], d["m_head_dim"], d["m_groups"], d["d_state"]
    T = config["chunk_size"]
    always = _always(per)
    here = d["top_k"] * d["n_experts"] / fix["n_experts_routed"]
    return {
        "decode_bytes_weights": BYTES * always,
        "decode_bytes_per_live_expert": BYTES * per["n_expert"]
        * per["expert"],
        "decode_bytes_per_kv_token":
            per["n_attn"] * BYTES * 2 * d["n_kv_heads"] * d["head_dim"],
        "decode_bytes_per_state_slot": per["n_mamba"] * 2 * (
            STATE_BYTES * per["state"] + BYTES * per["tail"]),
        "ssm_step_bytes_per_slot":
            per["n_mamba"] * 2 * STATE_BYTES * per["state"],
        "decode_bytes_per_window_slot": 0.0,
        "decode_flops_per_row": 2.0 * (
            always + per["n_expert"] * here * per["expert"]),
        "chunk_scan_bytes_per_token": per["n_mamba"] * (
            4 * (2 * Hm * P + Hm + 2 * Gm * N)
            + 2 * STATE_BYTES * per["state"]
            // config["serve"]["prefill_chunk"]),
        "chunk_scan_flops_per_token": per["n_mamba"] * (
            2 * Gm * T * N + Hm * (2 * T * P + 4 * N * P)),
    }


def train_consts(config: dict, seq: int) -> dict:
    """Operations forward and backward REQUIRE per token (6 per matmul
    parameter a token passes: top_k routed experts an expert layer, the
    head once) plus the attention layers' causal score/value term and
    the Mamba-2 layers' state update (4 Hm P N a layer, forward). No
    training cell runs this family; the count is here because a family
    has five functions."""
    d, per = configs.dims(config), layer_params(config)
    active = _always(per) + per["n_expert"] * d["top_k"] * per["expert"]
    mix = (12 * d["head_dim"] * per["n_attn"] * d["n_heads"] * seq
           + 3 * 4 * per["n_mamba"] * per["state"])
    return {"train_flops_per_token": 6.0 * active + mix}
