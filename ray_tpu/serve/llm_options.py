"""What `LLMEngine`'s options resolve to: once, here, by one rule.

A keyword left at None takes the fleet-wide knob (`runtime_config()`'s
`llm_*` field, `_KNOBS`). A feature the engine as configured cannot carry
— by itself, or with the configuration's model family
(models/serving.py `ServingFamily.unsupported`) — is settled by `_honour`:
the explicit constructor argument raises a typed error, the same value
arriving from the knob soft-disables — a fleet-wide `RAY_TPU_LLM_*`
export must not crash the replicas it does not fit.
Errors surface in the order of `resolve_options`' statements.
"""

from __future__ import annotations

import dataclasses
import types
from typing import Any

# (constructor keyword, runtime_config field): None means the knob.
_KNOBS = (
    ("page_size", "llm_kv_page_size"),
    ("attn_impl", "llm_attn_impl"),
    ("prefill_chunk", "llm_prefill_chunk"),
    ("prefill_token_budget", "llm_prefill_token_budget"),
    ("prefix_cache", "llm_prefix_cache"),
    ("prefix_cache_pages", "llm_prefix_cache_pages"),
    ("spec_draft", "llm_spec_draft"),
    ("spec_k", "llm_spec_k"),
    ("tp", "llm_tp"),
    ("kv_transfer", "llm_kv_transfer"),
    ("weight_dtype", "llm_weight_dtype"),
    ("kv_dtype", "llm_kv_dtype"),
    ("prefill_width_bucketing", "llm_prefill_width_bucketing"),
    ("warmup", "llm_warmup_compile"),
    ("decode_block", "llm_decode_block"),
)


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """As the engine runs them (a soft-disabled feature is neutral)."""

    page_size: int
    attn_impl: str                 # "gather" | "kernel" ("auto" resolved)
    prefill_chunk: int             # tokens of a prompt a chunk row carries
    prefill_token_budget: int
    prefix_cache: bool
    prefix_cache_pages: int
    spec_draft: Any                # "" = off, a name, or a GPTConfig
    spec_k: int
    draft_cfg: Any                 # the draft's GPTConfig, None = off
    tp: int
    mesh: Any                      # the ("tp",) mesh when tp > 1
    pool_role: str | None          # None | "prefill" | "decode"
    kv_transfer: bool
    kv_transfer_disabled_reason: str   # why the knob was turned off
    weight_dtype: str              # "bf16" | "int8"
    kv_dtype: str
    prefill_width_bucketing: bool
    warmup: bool
    decode_block: int


def _honour(o, explicit: set, name: str, fits, neutral,
            refusal: str | None) -> bool:
    """THE rule for option `name` when the engine cannot honour it
    (`fits` false): an explicit argument raises `refusal` (None = a later
    statement refuses it); the knob's value becomes `neutral`.
    → True when the knob was turned off."""
    if fits:
        return False
    if name in explicit:
        if refusal is not None:
            raise ValueError(refusal)
        return False
    setattr(o, name, neutral)
    return True


def resolve_options(cfg, *, max_len: int, spec_draft_params,
                    pool_role: str | None, **keywords) -> EngineOptions:
    """`keywords` are `_KNOBS`' constructor arguments as passed."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.models.serving import family_of

    o = types.SimpleNamespace(**keywords)
    explicit = {kw for kw, _field in _KNOBS if keywords[kw] is not None}
    if len(explicit) < len(_KNOBS):
        from ray_tpu.core.config import runtime_config

        rc = runtime_config()
        for kw, field in _KNOBS:
            if kw not in explicit:
                setattr(o, kw, getattr(rc, field))

    # What this configuration's model family cannot carry
    # (models/serving.py), by the one rule. A pool role asks for the
    # transfer as an argument does.
    for miss in family_of(cfg).unsupported:
        if miss.option == "kv_transfer" and pool_role:
            raise ValueError(miss.why)
        _honour(o, explicit, miss.option, miss.fits(o), miss.neutral,
                miss.why)

    # A prompt enters its slot's pages chunk by chunk, the one way in.
    # The knob beside a cache it does not fit (0, or longer than the
    # cache: chunked prompts are capped at max_len - 1, so a wider chunk
    # would only ever pad) takes the largest whole number of pages that
    # does, or the cache's length where one page is longer.
    refusal = (f"prefill_chunk ({o.prefill_chunk}) exceeds the KV cache "
               f"(max_len = {max_len})" if o.prefill_chunk > 0 else
               f"prefill_chunk must be positive, got {o.prefill_chunk}: "
               "one-shot admission (prefill_chunk=0) was removed in PR 64, "
               "a prompt enters its slot's pages chunk by chunk")
    _honour(o, explicit, "prefill_chunk", 0 < o.prefill_chunk <= max_len,
            max_len // o.page_size * o.page_size or max_len, refusal)
    if o.prefix_cache_pages < 0:
        raise ValueError(
            f"prefix_cache_pages must be >= 0, got {o.prefix_cache_pages}")
    if o.attn_impl == "auto":
        # The Pallas kernel on real TPUs (pages DMA'd in place — the
        # throughput path), the exact-semantics gather reference
        # everywhere else (off-TPU the kernel only runs under
        # interpret=True, slower than the XLA gather it would replace).
        # metrics()/load_snapshot() report the resolved value, so a
        # fleet-wide export of `auto` shows what each replica runs.
        o.attn_impl = ("kernel" if jax.default_backend() == "tpu"
                       else "gather")
    if o.attn_impl not in ("gather", "kernel"):
        raise ValueError(
            f"attn_impl must be gather|kernel|auto, got {o.attn_impl!r}")
    for name in ("weight_dtype", "kv_dtype"):
        if getattr(o, name) not in ("bf16", "int8"):
            raise ValueError(
                f"{name} must be bf16|int8, got {getattr(o, name)!r}")
    if o.prefill_token_budget != 0 and (
            o.prefill_token_budget < o.prefill_chunk):
        # A budget smaller than one chunk could never make progress on a
        # busy engine (and a negative budget would silently act like 0)
        # — reject the silent-deadlock config up front.
        raise ValueError(
            f"prefill_token_budget ({o.prefill_token_budget}) must be 0 "
            f"(pure-decode ticks) or >= prefill_chunk ({o.prefill_chunk})")
    if spec_draft_params is not None and not o.spec_draft:
        # Weights were supplied (a checkpoint was read off disk) but
        # nothing enables speculation — serving non-speculatively here
        # would silently discard them, with only a missing
        # spec_accepted_per_step metric as a hint.
        raise ValueError(
            "spec_draft_params supplied but speculative decoding is "
            "not enabled — set spec_draft / llm_spec_draft (and note "
            "the global knob soft-disables beside a model family that "
            "cannot carry it)")
    draft_cfg = None
    if o.spec_draft:
        if o.spec_k < 1:
            raise ValueError(
                f"llm_spec_k must be >= 1 (tokens the draft proposes "
                f"per slot per tick), got {o.spec_k}")
        draft_cfg = (o.spec_draft if isinstance(o.spec_draft, gpt.GPTConfig)
                     else gpt.GPTConfig.by_name(o.spec_draft))
        if draft_cfg.vocab_size != cfg.vocab_size:
            # Proposals index the target distribution by token id;
            # mismatched vocabs would silently verify garbage.
            raise ValueError(
                "speculative draft/target vocab mismatch: draft "
                f"vocab_size {draft_cfg.vocab_size} != target "
                f"vocab_size {cfg.vocab_size} (the tokenizer must be "
                "tied)")
    # tp=1 is byte-for-byte the single-chip engine (no mesh, no shard_map).
    o.tp = int(o.tp)
    if o.tp < 1:
        raise ValueError(f"llm_tp must be >= 1, got {o.tp}")

    def misfit(c):
        return c is not None and (c.n_heads % o.tp or c.d_ff % o.tp)

    if o.tp > 1 and "tp" not in explicit and (
            o.tp > len(jax.devices()) or misfit(cfg) or misfit(draft_cfg)):
        # The knob on a host or model it does not fit (too few devices,
        # a non-divisor): serve unsharded rather than refuse to boot.
        # Explicit arguments stay strict below; metrics()' llm_tp shows
        # the degrade.
        o.tp = 1
    mesh = None
    if o.tp > 1:
        # The mesh build IS the device-count validation (one spelling of
        # that error, models/partition.make_tp_mesh).
        from ray_tpu.models import partition as _partition

        mesh = _partition.make_tp_mesh(o.tp)
        if misfit(cfg):
            raise ValueError(
                f"llm_tp={o.tp} must divide the model's n_heads "
                f"({cfg.n_heads}) and d_ff ({cfg.d_ff}) — the KV pool "
                "shards along the head axis and the MLP along its "
                "hidden width")
        if misfit(draft_cfg):
            raise ValueError(
                f"llm_tp={o.tp} must divide the DRAFT model's n_heads "
                f"({draft_cfg.n_heads}) and d_ff ({draft_cfg.d_ff}) "
                "— the draft pool shards along the same head axis")
    # Disaggregated serving (core/config.py llm_kv_transfer): a role is
    # a fused engine's donate/adopt machinery split over two pools.
    if pool_role not in (None, "", "prefill", "decode"):
        raise ValueError(
            f"pool_role must be None|'prefill'|'decode', "
            f"got {pool_role!r}")
    pool_role = pool_role or None
    if pool_role is not None:
        if "kv_transfer" in explicit and not o.kv_transfer:
            raise ValueError(
                f"pool_role={pool_role!r} requires kv_transfer — the "
                "prefill→decode handoff IS a page-set donation + "
                "adoption")
        # A role asks for the transfer as surely as the argument does.
        o.kv_transfer = True
        explicit.add("kv_transfer")
    # chunk % page_size == 0 is load-bearing, not cosmetic: page-set
    # entries are deduped per chain DEPTH across donations, and with
    # page-aligned chunks every depth's span is self-contained. A
    # mid-page chunk boundary would let a chain compose depths from
    # DIFFERENT donations whose shared boundary page only one of them
    # fully wrote — adopting it would serve garbage KV for the boundary
    # positions and silently break byte-exactness. tp is NOT gated: tp>1
    # donors publish per-shard head planes and adopters reassemble/
    # re-slice at bind time (heads are shard-invariant math —
    # partition.split_head_planes).
    reason = (
        "KV page-set transfer requires prefill_chunk % page_size == 0 "
        "(cross-donation dedup needs page-aligned chain depths); got "
        f"prefill_chunk={o.prefill_chunk}, page_size={o.page_size}")
    # The one soft-disable that is never silent: the engine logs the
    # reason and exports it as kv_transfer_disabled_reason.
    disabled = _honour(
        o, explicit, "kv_transfer",
        not o.kv_transfer or o.prefill_chunk % o.page_size == 0,
        False, reason)
    return EngineOptions(
        draft_cfg=draft_cfg, mesh=mesh, pool_role=pool_role,
        kv_transfer_disabled_reason=reason if disabled else "", **vars(o))
