"""The `olmo_hybrid` family: Olmo-Hybrid-7B's block
(`ray_tpu.models.olmo_hybrid`: three Gated DeltaNet layers whose write
strength reaches 2, their float32 state of 96 x 192 a head living by the
slot beside the pages, to one MULTI-head attention layer of 30 x 128
with a norm over q's and k's whole width and no positions; every
sublayer's output normed; a dense gated-SiLU MLP in every layer; an
untied head), held to harness/reference/olmo_hybrid_ref.py. What a
family is, and what each function is for: harness/families.py.

The configuration file holds ONE PIPELINE STAGE of a four-chip host
under the keys of the public config.json: `num_hidden_layers` is the
leading layers run (the published count under `published`), every width
and the whole vocabulary as published.

The bytes and operations counted here, from the configuration's own
sizes (bf16 weights, pages and convolution tails, 2 B; the recurrent
state float32, 4 B):

  decode_bytes_weights          every matrix a step reads: each linear
      layer's W_q | W_k | W_v | W_g, W_b | W_a, convolutions and W_out,
      each full layer's W_q | W_k | W_v and W_o, every layer's three MLP
      matrices, and the head's [D, V] matrix. Not the embedding table (a
      step reads 96 rows of it), and not the vectors (norms, dt_bias,
      A_log: 0.3 MB): the count errs low.
  decode_bytes_per_kv_token     K and V of one cached token in the full
      layers: full layers x 2 x KV heads x head size x 2 B = 30,720 at
      two full layers of 30 x 128.
  decode_bytes_per_state_slot   one decoding slot's recurrent state and
      convolution tail in the linear layers, READ AND WRITTEN: linear
      layers x (H dk dv x 4 B + (taps - 1) x channels x 2 B) x 2 =
      27.4 MB at six linear layers. LOGICAL bytes: what the chip's
      layout of the leaf adds is `gdn_step_roofline`'s to show.
  decode_bytes_per_live_expert, decode_bytes_per_window_slot   0.0: no
      experts, no window layers (stated, so that a reader that sums a
      family's byte terms finds every one).
  decode_flops_per_row          2 x every matmul parameter a decoding
      row passes: what the MXU must do a row and step. The recurrence's
      own multiplies and adds (6 dk dv a head) run on the vector units
      and are left out, as is attention's score and value work.
  chunk_scan_bytes_per_token    what the chunked scan must move a prompt
      token, summed over the linear layers: q, k, v in and o out at
      float32 a head, and a 128-token row's state read and written,
      spread over its tokens.
  chunk_scan_flops_per_token    the scan's matmuls a token (blocks of 64
      tokens a head: k k^T and q k^T, the triangular inverse's merges
      (two matmuls a pair of neighbouring diagonal blocks, from 8
      tokens up: 1,344 T multiply-adds at T = 64; the 8-token blocks
      themselves are solved by substitution on the vector units), U and
      W, and the state pass's four), summed over the linear layers;
      counted once, whatever passes the precision costs.

Norms and activations are left out: the counts err low.
"""

from __future__ import annotations

import collections
import dataclasses
import types

from harness import configs

BYTES = 2           # bf16
STATE_BYTES = 4     # the recurrent state is float32
SCAN_BLOCK = 64     # tokens a block of the program's chunked scan
INVERSE_BASE = 8    # tokens a diagonal block solved by substitution

RefConfig = collections.namedtuple(
    "RefConfig", "n_layers full_interval n_heads n_kv_heads lin_heads "
    "lin_k_dim neg_eigval norm_eps")


def _program():
    from ray_tpu.models import olmo_hybrid

    return olmo_hybrid


def _alive(specs: dict) -> dict:
    """The program's `param_specs` with the two leaves that set the
    linear layers' decay as the benchmark seeds them, for the check's
    sake and for no other: `g_dt_bias` ~ N(0, 4) and `g_A_log` ~ N(0, 1)
    a head. harness/weights.py fills a leaf from its spec alone (a
    zero-mean normal, ones or zeros; anything else is zeros); under the
    layer's own start (`dt_bias` ones, A ~ U(0, 16)) a filler that knows
    no such init leaves `A_log` at zero and every head forgetting at the
    one rate of e^-1.3 a token, and under the start itself g ~ -10: the
    state is forgotten within one to three tokens and no fault in
    carrying it could show. softplus(N(0, 4)) times exp(N(0, 1)) spreads
    the heads' time constants from under one token to hundreds
    (families/qwen3_next.py `_alive` has the shares): the spread a
    trained model has, and what the state is for."""
    wide = lambda name, scale: {"shape": specs[name]["shape"],
                                "init": "normal", "scale": scale}
    return {**specs, "g_dt_bias": wide("g_dt_bias", 4.0),
            "g_A_log": wide("g_A_log", 1.0)}


def model():
    """What harness/families.py asks of a model module (no `loss_fn`:
    the family has no training form), with the benchmark's
    `param_specs`."""
    oh = _program()
    return types.SimpleNamespace(
        param_specs=lambda cfg: _alive(oh.param_specs(cfg)),
        partition_rules=oh.partition_rules, init_params=oh.init_params)


def _checked(config: dict) -> dict:
    """The file's sizes under the program's names, with what the keys
    the program does not take as numbers say: after those have been held
    to what it builds."""
    built = {"hidden_act": "silu", "attention_bias": False,
             "tie_word_embeddings": False,
             "rope_parameters": {"rope_theta": None}}
    wrong = {k: config.get(k) for k, v in built.items()
             if config.get(k) != v}
    if wrong:
        raise SystemExit(f"the olmo_hybrid family builds {built}; the "
                         f"configuration says {wrong}")
    d = configs.dims(config)
    if d["n_heads"] * d["head_dim"] != d["d_model"]:
        raise SystemExit("the olmo_hybrid family's heads are d_model / "
                         "n_heads wide (config.json has no head_dim)")
    if d["lin_k_heads"] != d["lin_v_heads"]:
        raise SystemExit("the olmo_hybrid family has a key head a value head")
    kinds = config["layer_types"][:d["n_layers"]]
    full = [l for l, kind in enumerate(kinds) if kind == "full_attention"]
    interval = full[0] + 1 if full else 0
    if (not interval or kinds != [
            "full_attention" if (l + 1) % interval == 0
            else "linear_attention" for l in range(d["n_layers"])]):
        raise SystemExit("the olmo_hybrid family builds a full layer every "
                         f"n-th layer; `layer_types` starts {kinds}")
    return {**d, "full_interval": interval,
            "allow_neg_eigval": bool(config["linear_allow_neg_eigval"])}


def program_config(config: dict, **overrides):
    d = _checked(config)
    fields = {f.name
              for f in dataclasses.fields(_program().OlmoHybridConfig)}
    kwargs = {**configs.program_kwargs(config, **overrides),
              "full_interval": d["full_interval"],
              "allow_neg_eigval": d["allow_neg_eigval"]}
    return _program().OlmoHybridConfig(**{k: v for k, v in kwargs.items()
                                          if k in fields})


def reference_config(config: dict) -> RefConfig:
    d = _checked(config)
    return RefConfig(
        n_layers=d["n_layers"], full_interval=d["full_interval"],
        n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
        lin_heads=d["lin_v_heads"], lin_k_dim=d["lin_k_dim"],
        neg_eigval=d["allow_neg_eigval"], norm_eps=d["norm_eps"])


def layer_params(config: dict) -> dict:
    """Matmul parameters by part: one linear layer's and one full
    layer's mixer, a layer's MLP, the head; how many layers of each
    kind; and a linear layer's state and tail, in elements."""
    d = _checked(config)
    D, H, G, K = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    Hl, dk, dv = d["lin_v_heads"], d["lin_k_dim"], d["lin_v_dim"]
    channels = Hl * (2 * dk + dv)
    n_full = d["n_layers"] // d["full_interval"]
    return {
        "linear": (D * (channels + Hl * dv) + D * 2 * Hl
                   + d["conv_taps"] * channels + Hl * dv * D),
        "full": D * (H + 2 * G) * K + H * K * D,
        "mlp": 3 * D * d["d_ff"], "head": D * d["vocab_size"],
        "n_full": n_full, "n_linear": d["n_layers"] - n_full,
        "state": Hl * dk * dv, "tail": (d["conv_taps"] - 1) * channels,
    }


def n_matmul_params(config: dict) -> int:
    """Every matrix a decode step reads (the head, not the embedding)."""
    per = layer_params(config)
    return (per["n_linear"] * per["linear"] + per["n_full"] * per["full"]
            + (per["n_linear"] + per["n_full"]) * per["mlp"] + per["head"])


def serve_consts(config: dict) -> dict:
    d, per = _checked(config), layer_params(config)
    H, dk, dv = d["lin_v_heads"], d["lin_k_dim"], d["lin_v_dim"]
    T = SCAN_BLOCK
    merges, t = 0, INVERSE_BASE
    while t < T:                 # T / 2t pairs, two t^3 matmuls a pair
        merges += (T // (2 * t)) * 2 * t ** 3
        t *= 2
    block_flops = 2 * (
        2 * T * T * dk                       # k k^T, q k^T
        + merges                             # the triangular inverse
        + T * T * dv + T * T * dk            # U, W
        + 2 * T * dk * dv + T * T * dv + T * dk * dv)    # the state pass
    matmul = n_matmul_params(config)
    return {
        "decode_bytes_weights": BYTES * matmul,
        "decode_bytes_per_kv_token":
            per["n_full"] * BYTES * 2 * d["n_kv_heads"] * d["head_dim"],
        "decode_bytes_per_state_slot": per["n_linear"] * 2 * (
            STATE_BYTES * per["state"] + BYTES * per["tail"]),
        "decode_bytes_per_live_expert": 0.0,
        "decode_bytes_per_window_slot": 0.0,
        "decode_flops_per_row": 2.0 * matmul,
        "chunk_scan_bytes_per_token": per["n_linear"] * (
            4 * H * (2 * dk + 2 * dv)
            + 2 * STATE_BYTES * per["state"]
            // config["serve"]["prefill_chunk"]),
        "chunk_scan_flops_per_token":
            per["n_linear"] * H * block_flops // T,
    }


def train_consts(config: dict, seq: int) -> dict:
    """Operations forward and backward REQUIRE per token (6 per matmul
    parameter, the head once) plus the full layers' causal score/value
    term and the linear layers' state update (4 dk dv a head). No
    training cell runs this family; the count is here because a family
    has five functions."""
    d, per = _checked(config), layer_params(config)
    mix = (12 * d["head_dim"] * per["n_full"] * d["n_heads"] * seq
           + 3 * 4 * per["n_linear"] * per["state"])
    return {"train_flops_per_token": 6.0 * n_matmul_params(config) + mix}
