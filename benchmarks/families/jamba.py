"""The `jamba` family: AI21-Jamba2-3B's block (`ray_tpu.models.jamba`:
thirteen Mamba-1 selective-scan layers, whose float32 state lives by the
slot beside the pages, to one multi-query attention layer without
positions; a dense gated-SiLU MLP in every layer; a tied head), held to
harness/reference/jamba_ref.py. What a family is, and what each function
is for: harness/families.py.

The configuration file holds the WHOLE model under the keys of the
public config.json: one chip serves every layer and every vocabulary
row, and `reduced` is empty.

The bytes and operations counted here, from the configuration's own
sizes (bf16 weights, pages and convolution tails, 2 B; the state-space
state float32, 4 B):

  decode_bytes_weights          every matrix a step reads: each mamba
      layer's W_in, W_x, W_dt, W_out, each attention layer's W_q, W_k,
      W_v, W_o, every layer's three MLP matrices, and the tied table ONCE,
      as the head (the embedding lookup reads 256 rows of it, not the
      table). The vectors (norms, biases, taps, A_log, D: 3.6 MB) are
      left out: the count errs low.
  decode_bytes_per_kv_token     K and V of one cached token in the
      attention layers: layers x 2 x KV heads x head size x 2 B = 1,024.
  decode_bytes_per_state_slot   one decoding slot's state and
      convolution tail in the mamba layers, READ AND WRITTEN: mamba
      layers x (S Dn x 4 B + (taps - 1) Dn x 2 B) x 2 = 18.6 MB.
  ssm_step_bytes_per_slot       the state alone, read and written: what
      the decode step's scan moves (`ssm_step_roofline`; the tail moves
      in `ssm.in`): mamba layers x S Dn x 4 B x 2 = 17.0 MB.
  decode_bytes_per_live_expert, decode_bytes_per_window_slot   0.0: no
      experts, no window layers (stated, so that a reader that sums a
      family's byte terms finds every one).
  decode_flops_per_row          2 x every matmul parameter a decoding
      row passes (the tied table once, as the head): what the MXU must
      do a row and step. The scan's own multiplies and adds (6 S Dn a
      mamba layer) run on the vector units and are left out, as is
      attention's score and value work (it grows with the context).
  chunk_scan_bytes_per_token    what a chunk program's scan must move a
      prompt token, summed over the mamba layers: xs and dt in and y out
      at float32 a channel, B and C in, and a 128-token row's state read
      and written, spread over its tokens.

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
    "RefConfig", "n_layers attn_period attn_offset n_heads n_kv_heads "
    "norm_eps")


def _program():
    from ray_tpu.models import jamba

    return jamba


def _alive(specs: dict) -> dict:
    """The program's `param_specs` with three stacks of vectors as the
    benchmark seeds them, for the check's sake and for no other.
    harness/weights.py fills a leaf from its spec alone (a zero-mean
    normal, ones or zeros; anything else is zeros):

      m_dt_b ~ N(0, 4), m_A_log ~ N(0, 1) a (state, channel) pair. Under
          the model's own start (A = 1..16, steps of 1e-3 to 1e-1) a
          filler that knows no such init leaves both at ZERO: a step of
          softplus(W_dt r) ~ 0.7 and A = -1 everywhere, every pair
          forgetting at the one rate of e^-0.7 a token, and no fault in
          carrying the state past a few tokens could show. With W_dt r
          ~ N(0, 1) under it, softplus(N(0, 4)) times exp(N(0, 1))
          spreads the pairs' time constants 1 / (dt |A|) from under one
          token to thousands: 42 % forget within a token, 27 % remember
          more than 15 tokens, 12 % more than 150, 4 % more than 1,500
          (2 M draws): the spread a trained model has, and what the
          state is for.
      ln_f_scale ~ N(0, 1), not ones. With a TIED head and random
          weights the final hidden state is mostly the input token's own
          embedding, and a scale of ones makes every position predict
          its input by a margin no arithmetic can move (PERF.md section
          6, PR 33; families/zaya.py). A random sign and size a channel
          takes the self-similarity away and leaves the logits to the
          layers."""
    wide = lambda name, scale: {"shape": specs[name]["shape"],
                                "init": "normal", "scale": scale}
    return {**specs, "m_dt_b": wide("m_dt_b", 4.0),
            "m_A_log": wide("m_A_log", 1.0),
            "ln_f_scale": wide("ln_f_scale", 1.0)}


def model():
    """What harness/families.py asks of a model module (no `loss_fn`:
    the family has no training form), with the benchmark's
    `param_specs`."""
    jm = _program()
    return types.SimpleNamespace(
        param_specs=lambda cfg: _alive(jm.param_specs(cfg)),
        partition_rules=jm.partition_rules, init_params=jm.init_params)


def _checked(config: dict) -> dict:
    """The file's sizes under the program's names, after the keys the
    program does not take have been held to what it builds."""
    built = {"num_experts": 1, "num_experts_per_tok": 1,
             "mamba_conv_bias": True, "mamba_proj_bias": False,
             "tie_word_embeddings": True, "hidden_act": "silu",
             "sliding_window": None}
    wrong = {k: config.get(k) for k, v in built.items()
             if config.get(k) != v}
    if wrong:
        raise SystemExit(f"the jamba family builds {built}; the "
                         f"configuration says {wrong}")
    d = configs.dims(config)
    if d["n_heads"] * d["head_dim"] != d["d_model"]:
        raise SystemExit("the jamba family's heads are d_model / n_heads "
                         "wide (config.json has no head_dim)")
    return d


def program_config(config: dict, **overrides):
    _checked(config)
    fields = {f.name for f in dataclasses.fields(_program().JambaConfig)}
    kwargs = configs.program_kwargs(config, **overrides)
    return _program().JambaConfig(**{k: v for k, v in kwargs.items()
                                     if k in fields})


def reference_config(config: dict) -> RefConfig:
    d = _checked(config)
    return RefConfig(**{f: d[f] for f in RefConfig._fields})


def layer_params(config: dict) -> dict:
    """Matmul parameters by part: one mamba layer's and one attention
    layer's mixer, a layer's MLP, the tied table; how many layers of
    each kind; a mamba layer's state and tail, in elements; and the
    vectors' total."""
    d = _checked(config)
    D, H, G, K, F = (d["d_model"], d["n_heads"], d["n_kv_heads"],
                     d["head_dim"], d["d_ff"])
    Dn, S, R, taps = (d["expand"] * D, d["d_state"], d["dt_rank"],
                      d["d_conv"])
    n_attn = sum(l % d["attn_period"] == d["attn_offset"]
                 for l in range(d["n_layers"]))
    n_mamba = d["n_layers"] - n_attn
    return {
        "mamba": D * 2 * Dn + Dn * (R + 2 * S) + R * Dn + Dn * D,
        "attn": D * H * K + 2 * D * G * K + H * K * D,
        "mlp": 3 * D * F, "table": d["vocab_size"] * D,
        "n_attn": n_attn, "n_mamba": n_mamba,
        "state": S * Dn, "tail": (taps - 1) * Dn,
        "vectors": (n_mamba * (taps * Dn + 3 * Dn + S * Dn + R + 2 * S)
                    + 2 * d["n_layers"] * D + D),
    }


def n_params(config: dict) -> int:
    """Every parameter, the tied table once."""
    per = layer_params(config)
    return (per["n_mamba"] * per["mamba"] + per["n_attn"] * per["attn"]
            + (per["n_mamba"] + per["n_attn"]) * per["mlp"] + per["table"]
            + per["vectors"])


def serve_consts(config: dict) -> dict:
    d, per = _checked(config), layer_params(config)
    matmul = n_params(config) - per["vectors"]
    Dn, S = d["expand"] * d["d_model"], d["d_state"]
    return {
        "decode_bytes_weights": BYTES * matmul,
        "decode_bytes_per_kv_token":
            per["n_attn"] * BYTES * 2 * d["n_kv_heads"] * d["head_dim"],
        "decode_bytes_per_state_slot": per["n_mamba"] * 2 * (
            STATE_BYTES * per["state"] + BYTES * per["tail"]),
        "ssm_step_bytes_per_slot":
            per["n_mamba"] * 2 * STATE_BYTES * per["state"],
        "decode_bytes_per_live_expert": 0.0,
        "decode_bytes_per_window_slot": 0.0,
        "decode_flops_per_row": 2.0 * matmul,
        "chunk_scan_bytes_per_token": per["n_mamba"] * (
            4 * (3 * Dn + 2 * S)
            + 2 * STATE_BYTES * per["state"]
            // config["serve"]["prefill_chunk"]),
    }


def train_consts(config: dict, seq: int) -> dict:
    """Operations forward and backward REQUIRE per token (6 per matmul
    parameter, the tied table once as the head) plus the attention
    layers' causal score/value term and the mamba layers' state update
    (6 S Dn a layer, forward). No training cell runs this family; the
    count is here because a family has five functions."""
    d, per = _checked(config), layer_params(config)
    matmul = n_params(config) - per["vectors"]
    mix = (12 * d["head_dim"] * per["n_attn"] * d["n_heads"] * seq
           + 3 * 6 * per["n_mamba"] * per["state"])
    return {"train_flops_per_token": 6.0 * matmul + mix}
