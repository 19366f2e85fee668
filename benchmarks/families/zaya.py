"""The `zaya` family: ZAYA1's block (`ray_tpu.models.zaya`: compressed
convolutional attention in grouped-query form with a per-slot conv/shift
state, a top-1 expert layer behind an MLP router, scaled residuals, a
tied head), held to harness/reference/zaya_ref.py. What a family is, and
what each function is for: harness/families.py.

The bytes a decode step must read are counted here, from the
configuration's own sizes (bf16, 2 B a parameter):

  decode_bytes_weights         everything a step reads WHATEVER the
      routing: per layer W_q, W_k, W_v1 + W_v2, W_o, the two
      convolutions and the router's four matrices; once, the tied
      embedding as the head's [V, D] matrix. No expert.
  decode_bytes_per_live_expert one expert's three matrices (3 D F) times
      the layers: multiplied by the MEAN number of experts that had a
      row in a layer of a step (`experts_touched`, the program's
      counter), so that a roofline share counts only experts a token
      reached and errs low (the layer streams all it holds).
  decode_bytes_per_kv_token    K and V of one cached token: layers x 2 x
      KV heads x head size.

Norms, biases, scales, the slot state (5.4 KB a slot and layer) and the
activations are left out: the count errs low.

The seeded weights a cell runs are made HERE, not by the program's
initialisation: `model()` is the program's module with a `param_specs`
that moves two leaves off it, so that the `correct` rule has something
to see (`_check_visible`).
"""

from __future__ import annotations

import collections
import dataclasses
import math
import types

from harness import configs

BYTES = 2       # bf16

RefConfig = collections.namedtuple(
    "RefConfig", "n_heads n_kv_heads rotary_dim rope_theta norm_eps")


def _check_visible(specs: dict, cfg) -> dict:
    """The program's `param_specs` (scales 1, biases 0, W_o and W_down
    at the residual scale 0.02 / sqrt(2 L)) with two leaves as the
    benchmark seeds them, each for the check's sake and for no other:

      ln_f_scale  N(0, 1), not ones. With a TIED head and random weights
          the final hidden state is mostly the input token's own
          embedding, and a scale of ones makes every position predict its
          input by a margin no arithmetic can move (top-1 agreement with
          float32 0.9999, deficits of 1e-6: a check that sees nothing;
          PERF.md section 6, PR 33). A random sign and size a channel
          takes the self-similarity away and leaves the logits to the
          layers.
      w_down      twice the residual scale: a token's one expert then
          moves its logits enough that a wrong expert shows.

    The balancing bias r_beta stays 0: the model's balancing rule needs
    the router run over seeded tokens, and harness/weights.py fills
    leaves from their specs alone."""
    return {**specs,
            "ln_f_scale": {**specs["ln_f_scale"], "init": "normal",
                           "scale": 1.0},
            "w_down": {**specs["w_down"], "init": "normal",
                       "scale": 0.04 / math.sqrt(2 * cfg.n_layers)}}


def _program():
    from ray_tpu.models import zaya

    return zaya


def model():
    """What harness/families.py asks of a model module (no `loss_fn`:
    the family has no training form), with the benchmark's
    `param_specs`."""
    zaya = _program()
    return types.SimpleNamespace(
        param_specs=lambda cfg: _check_visible(zaya.param_specs(cfg), cfg),
        partition_rules=zaya.partition_rules,
        logical_axes=zaya.logical_axes, init_params=zaya.init_params)


def _rotary(config: dict) -> dict:
    if config["num_experts_per_tok"] != 1:
        raise SystemExit("the zaya family routes top-1")
    return {"rotary_dim": int(config["partial_rotary_factor"]
                              * config["head_dim"]),
            "rope_theta": float(
                config["rope_parameters"]["hybrid"]["rope_theta"])}


def program_config(config: dict, **overrides):
    fields = {f.name for f in dataclasses.fields(_program().ZayaConfig)}
    kwargs = {**configs.program_kwargs(config, **overrides),
              **_rotary(config)}
    return _program().ZayaConfig(**{k: v for k, v in kwargs.items()
                                    if k in fields})


def reference_config(config: dict) -> RefConfig:
    return RefConfig(n_heads=config["num_attention_heads"],
                     n_kv_heads=config["num_key_value_heads"],
                     norm_eps=config["rms_norm_eps"], **_rotary(config))


def layer_params(config: dict) -> dict:
    """Matmul parameters of ONE layer, by part."""
    d = configs.dims(config)
    D, K, R = d["d_model"], d["head_dim"], d["router_dim"]
    hk, gk = d["n_heads"] * K, d["n_kv_heads"] * K
    return {
        "attention": (D * hk + D * gk + D * gk + hk * D      # q, k, v, o
                      + 2 * (hk + gk) + 2 * (hk + gk) * K),  # the two convs
        "router": D * R + 2 * R * R + R * d["n_experts"],
        "expert": 3 * D * d["d_ff"],
    }


def serve_consts(config: dict) -> dict:
    d = configs.dims(config)
    per = layer_params(config)
    L = d["n_layers"]
    return {
        "decode_bytes_weights": BYTES * (
            L * (per["attention"] + per["router"])
            + d["vocab_size"] * d["d_model"]),
        "decode_bytes_per_live_expert": BYTES * L * per["expert"],
        "decode_bytes_per_kv_token":
            BYTES * L * 2 * d["n_kv_heads"] * d["head_dim"],
        # `decode_stream_roofline` sums five byte terms and reads nothing
        # where one is missing: this family has no window layer (every
        # layer reads every cached token) and no recurrent state (the
        # conv/shift state is 5.4 KB a slot and layer, left out above).
        "decode_bytes_per_window_slot": 0.0,
        "decode_bytes_per_state_slot": 0.0,
    }


def train_consts(config: dict, seq: int) -> dict:
    """Operations forward and backward REQUIRE per token (6 per matmul
    parameter a token passes: one expert of the layer's 16, the head
    once) plus the causal score/value term over the query latent. No
    training cell runs this family; the count is here because a family
    has five functions."""
    d = configs.dims(config)
    per = layer_params(config)
    active = d["n_layers"] * (per["attention"] + per["router"]
                              + per["expert"])
    attn = 12 * d["n_layers"] * d["n_heads"] * d["head_dim"] * seq
    return {"train_flops_per_token":
            6.0 * (active + d["vocab_size"] * d["d_model"]) + attn}
