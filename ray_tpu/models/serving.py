"""The serving seam: what `LLMEngine` needs of a model family.

The engine's scheduler, tick, page accounting and host phases are the
same for every architecture; what differs is the device side: how the
pool pytree is built (K/V pages, and for some families a per-slot
state beside them), which jitted programs a chunk and a decode window
run, how weights and pool shard, and which of the engine's options the
family's programs cannot carry. A configuration object names its family
(a class attribute `family`; `GPTConfig` carries none and is "gpt") and
`family_of(cfg)` hands the engine one `ServingFamily`: the mirror, on
the program's side, of benchmarks/families/<family>.py. Nothing in
serve/llm.py names a model class.

A new family writes a model module (a configuration class, `param_specs`
/ `init_params` / `partition_rules`, the full-sequence `forward` its
tests compare with, `init_paged_kv`, ONE chunk forward, ONE decode step
and a head, handed to `paged_kv.paged_programs`; the parts it shares with
others come from models/blocks.py) and a row here: what its pool keeps by
the slot beside the pages, and the clause each of `_REFUSALS` needs about
that.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable


@dataclasses.dataclass(frozen=True)
class Unsupported:
    """An engine option this family's programs cannot carry.
    `fits(o)`: the resolved options as they stand are fine; `neutral`:
    what a fleet-wide knob is turned to; `why`: the explicit argument's
    error, naming what would have to be built
    (serve/llm_options.py `_honour`)."""

    option: str
    fits: Callable[[Any], bool]
    neutral: Any
    why: str


@dataclasses.dataclass(frozen=True)
class ServingFamily:
    name: str
    # The model module: init_params(cfg, key), partition_rules(), and
    # quantize_params(params) where int8 weights are supported.
    model: Any
    # (cfg, n_pages, page_size, n_slots, kv_dtype) -> the pool pytree the
    # paged programs carry (donated): {"k", "v", ...}.
    init_pool: Callable
    pool_partition_rules: tuple
    # (tp, mesh) -> {name: callable}: the programs `_bind_programs` wraps.
    programs: Callable
    # The pool's leaves that are a state by the slot, beside the pages
    # (() for none): chunk programs are told each row's slot
    # (`slots=`), and `metrics()["slot_state_bytes"]` counts them.
    slot_state: tuple = ()
    # The pool keeps some layers' K/V in a ring of pages a slot, beside
    # the pages `PagePool` accounts for: chunk programs are told each
    # row's slot (`slots=`), and `init_pool` is told how many tokens of
    # one prompt a chunk dispatch may carry (`dispatch_tokens=`).
    slot_ring: bool = False
    # (cfg, params) -> params: the tree as the family's programs want it
    # laid out, applied once at load (None: as it came).
    lay_out: Callable | None = None
    unsupported: tuple = ()

    @property
    def expert_counters(self) -> tuple:
        """What the decode programs' running counters in the pool count
        (the names the module gave the program builder, `model.COUNTERS`;
        () for none): a decode window hands them over with its tokens
        (`counters=`)."""
        return getattr(self.model, "COUNTERS", ())


_PAGED = ("prefill_chunk_paged", "verify_chunk_paged", "decode_step_paged",
          "decode_multi_paged", "copy_pages", "gather_pages",
          "scatter_pages", "spec_draft_propose")


def _gpt_programs(tp: int, mesh) -> dict:
    """models/paged_kv.py's set; at tp > 1 the programs are their
    shard_map twins (`*_tp`) with the mesh bound as a static kwarg,
    under the same names."""
    from ray_tpu.models import paged_kv

    return {name: (getattr(paged_kv, name) if tp == 1 else
                   functools.partial(getattr(paged_kv, name + "_tp"),
                                     mesh=mesh))
            for name in _PAGED}


def _gpt() -> ServingFamily:
    from ray_tpu.models import gpt, paged_kv

    return ServingFamily(
        name="gpt", model=gpt,
        init_pool=lambda cfg, n_pages, page_size, _n_slots, kv_dtype:
            paged_kv.init_paged_kv(cfg, n_pages, page_size,
                                   kv_dtype=kv_dtype),
        pool_partition_rules=paged_kv.KV_POOL_PARTITION_RULES,
        programs=_gpt_programs)


# The engine options that the programs of a family with a memory by the
# slot beside the pages cannot carry (with experts or without), in the order
# serve/llm_options.py settles them: (option, `fits`, `neutral`, the
# refusal). A refusal names the family ({name}) and says what would have to
# be built; where that depends on WHAT the family keeps by the slot
# ({beside}: a one-token state, a recurrence, a ring) or on whether it has
# experts, it takes the family's own clause under the option's name.
_REFUSALS = (
    ("prefill_width_bucketing",
     lambda o: not o.prefill_width_bucketing, False,
     "prefill_width_bucketing with the {name} family: a chunk program "
     "here costs {prefill_width_bucketing} at any table width, and one "
     "bucket a width spreads a lone prompt's rows over more programs; a "
     "dispatch that packs rows of several widths into one program would "
     "have to be built"),
    ("prefix_cache", lambda o: not o.prefix_cache, False,
     "prefix_cache with the {name} family: a cached prefix would need "
     "{prefix_cache} (serve/prefix_cache.py keeps PagePool pages only)"),
    ("spec_draft", lambda o: not o.spec_draft, "",
     "speculative decoding with the {name} family: a rejected proposal "
     "rewinds the cursor, and {spec_draft} would have to be built"),
    ("kv_transfer", lambda o: not o.kv_transfer, False,
     "KV page-set transfer with the {name} family: a page set would have "
     "to carry {kv_transfer}"),
    ("tp", lambda o: int(o.tp) == 1, 1,
     "tp > 1 with the {name} family: {tp}"),
    ("weight_dtype", lambda o: o.weight_dtype != "int8", "bf16",
     "weight_dtype='int8' with the {name} family: quantize_params knows "
     "the gpt tree's planes, {weight_dtype}"),
    ("kv_dtype", lambda o: o.kv_dtype != "int8", "bf16",
     "kv_dtype='int8' with the {name} family: the per-page scale planes "
     "are kept by models/paged_kv._quant_write, {kv_dtype}"),
)

_SNAPSHOT = ("a snapshot of {beside} at the prefix's boundary, stored with "
             "its pages")
_RETURNS = ("a verify program that returns {beside} at every position to "
            "rewind to")
_EXCHANGE = ("the experts need an expert-parallel exchange of rows between "
             "chips (ops/moe.py returns the held experts' part only)")
# What a family WITH experts says under the two options whose refusal
# turns on them.
_EXPERTS = {
    "prefill_width_bucketing": "a pass over every held expert's weights",
    "weight_dtype": "and the experts' grouped matmul (ops/moe.py) has no "
                    "int8 form"}


def _paged_only(model, beside: str, clauses: dict, **fields) -> ServingFamily:
    """A family served from the paged pool alone, by the four programs
    its module binds from `paged_kv.paged_programs`, every leaf
    replicated: `beside` is what its pool keeps by the slot beside the
    pages, `clauses` what each of `_REFUSALS` needs said about that."""
    from jax.sharding import PartitionSpec

    name = model.__name__.rpartition(".")[2]
    clauses = {"kv_transfer": "{beside} (serve/kv_objects.py moves PagePool "
                              "pages only)", **clauses}
    said = {option: clause.format(beside=beside)
            for option, clause in clauses.items()}
    return ServingFamily(
        name=name, model=model, init_pool=model.init_paged_kv,
        pool_partition_rules=((r".*", PartitionSpec()),),
        programs=lambda _tp, _mesh: {
            program: getattr(model, program) for program in (
                "prefill_chunk_paged", "decode_step_paged",
                "decode_multi_paged")},
        unsupported=tuple(
            Unsupported(option, fits, neutral,
                        why.format(name=name, beside=beside, **said))
            for option, fits, neutral, why in _REFUSALS),
        **fields)


def _zaya() -> ServingFamily:
    from ray_tpu.models import zaya

    return _paged_only(
        zaya,
        "the slot's conv/shift state (z, c and W_v2 u of its last token, "
        "per layer: models/zaya.py)",
        {**_EXPERTS,
         "prefix_cache": _SNAPSHOT, "spec_draft": _RETURNS,
         "tp": "2 KV heads cannot shard over more chips than heads "
               "(models/partition.py and serve/kv_objects.py split the "
               "pool by whole heads), and the experts need an "
               "expert-parallel dispatch, not a head split",
         "kv_dtype": "which the CCA block's K/V writer would have to call"},
        slot_state=("slot_state",))


def _ring(model) -> ServingFamily:
    """A family whose window layers keep a ring of pages a slot beside
    the full layers' pages (models/laguna.py `ring_pool`)."""
    return _paged_only(
        model,
        "the window layers' ring of pages a slot (models/laguna.py: "
        "indexed by slot, outside PagePool's page ids)",
        {**_EXPERTS,
         "prefix_cache": "{beside} at the prefix's boundary stored with "
                         "its pages: a window kind whose pages can be "
                         "shared",
         "spec_draft": "a ring whose newest pages overwrote the oldest "
                       "cannot be rewound past them; a verify program over "
                       "a ring with room for the proposals",
         "tp": _EXCHANGE + ", not a head split, and no partition rule "
               "shards a ring",
         "kv_dtype": "and the kernels' window form takes a bf16 pool "
                     "(ops/paged_attention.py)"},
        slot_ring=True)


def _laguna() -> ServingFamily:
    from ray_tpu.models import laguna

    return _ring(laguna)


def _mimo_v2() -> ServingFamily:
    from ray_tpu.models import mimo_v2

    return _ring(mimo_v2)


def _qwen3_next() -> ServingFamily:
    from ray_tpu.models import qwen3_next

    return _paged_only(
        qwen3_next,
        "the linear layers' recurrent state and convolution tail "
        "(models/qwen3_next.py: a float32 matrix a head and layer, "
        "12.9 MB a slot at the published sizes, indexed by slot)",
        {**_EXPERTS,
         "prefix_cache": _SNAPSHOT,
         "spec_draft": "a recurrence cannot be run backwards; " + _RETURNS,
         "tp": "2 KV heads cannot shard over more chips than heads, "
               + _EXCHANGE + ", and no partition rule splits the recurrent "
               "state by value head",
         "kv_dtype": "which the gated attention's K/V writer would have to "
                     "call, and the recurrent state is float32 by the "
                     "model's own definition"},
        slot_state=qwen3_next.SLOT_STATE_LEAVES, lay_out=qwen3_next.lay_out)


def _jamba() -> ServingFamily:
    from ray_tpu.models import jamba

    return _paged_only(
        jamba,
        "the mamba layers' state-space state and convolution tail "
        "(models/jamba.py: 16 float32 values a channel and layer, 9.3 MB a "
        "slot at the published sizes, indexed by slot)",
        {"prefill_width_bucketing": "a pass over all of the model's "
                                    "weights (every layer is dense)",
         "prefix_cache": _SNAPSHOT,
         "spec_draft": "a recurrence cannot be run backwards; " + _RETURNS,
         "tp": "one KV head cannot shard over more chips than heads "
               "(models/partition.py and serve/kv_objects.py split the "
               "pool by whole heads), and no partition rule splits the "
               "state-space state and the mixer's matrices by channel",
         "weight_dtype": "and an int8 form of this family's tree (a plane a "
                         "layer, with its scale vectors) would have to be "
                         "written",
         "kv_dtype": "which the attention layers' K/V writer would have to "
                     "call, and the state-space state is float32: it "
                     "accumulates thousands of steps"},
        slot_state=jamba.SLOT_STATE_LEAVES)


def _kimi_k2() -> ServingFamily:
    """The first newer family with NOTHING by the slot: its cache is one
    latent row a token and layer in `PagePool`'s own pages, so the
    features below are nearer here than for any family above, and each
    clause names the module that still speaks of K and V planes with a
    head axis."""
    from ray_tpu.models import kimi_k2

    return _paged_only(
        kimi_k2,
        "the latent plane `kv` (models/kimi_k2.py: ONE row of 576 values a "
        "token and layer with no head axis, in PagePool's own pages; "
        "nothing is kept by the slot)",
        {**_EXPERTS,
         "prefix_cache": "no snapshot, the pages are PagePool's: "
                         "serve/prefix_cache.py's copy-on-write and the "
                         "engine's page programs (models/paged_kv.py "
                         "copy_pages) would have to learn {beside} where "
                         "they name `k` and `v`",
         "spec_draft": "a verify program over {beside} (the rewind itself "
                       "is the cursor's alone here) and a draft model of "
                       "this family",
         "kv_transfer": "{beside}: serve/kv_objects.py names `k` and `v` "
                        "planes, splits them by a head axis, and its wire "
                        "fingerprint names the full-head layout",
         "tp": _EXCHANGE + ", and models/partition.py has no rule for a "
               "row without heads: the latent plane would be replicated "
               "and the query heads split, or the row split within "
               "itself",
         "kv_dtype": "which a one-plane writer (blocks.write_row) would "
                     "have to call, and the kernels' latent form takes a "
                     "bf16 plane (ops/paged_attention.py)"},
        lay_out=kimi_k2.lay_out)


def _olmo_hybrid() -> ServingFamily:
    """qwen3_next's two memories (a recurrent state by the slot beside
    pages) without experts, as jamba is: every layer dense, the pages
    MULTI-head (30 KV heads), the tree served as it comes (no
    `lay_out`)."""
    from ray_tpu.models import olmo_hybrid

    return _paged_only(
        olmo_hybrid,
        "the linear layers' recurrent state and convolution tail "
        "(models/olmo_hybrid.py: a float32 matrix of 96 x 192 a head and "
        "layer, two heads side by side, 13.7 MB a slot at the published "
        "sizes, indexed by slot)",
        {"prefill_width_bucketing": "a pass over all of the model's "
                                    "weights (every layer is dense)",
         "prefix_cache": _SNAPSHOT,
         "spec_draft": "a recurrence cannot be run backwards; " + _RETURNS,
         "tp": "30 heads of each kind divide by neither 4 nor 8 chips, no "
               "partition rule splits the recurrent state by value head, "
               "and the deployment this family stands for splits the "
               "LAYERS over a host's chips (pipeline stages), which "
               "serve/ does not build",
         "weight_dtype": "and an int8 form of this family's tree (a plane a "
                         "layer, with its scale vectors) would have to be "
                         "written",
         "kv_dtype": "which the full layers' K/V writer would have to call, "
                     "and the recurrent state is float32 by the model's own "
                     "definition"},
        slot_state=olmo_hybrid.SLOT_STATE_LEAVES)


def _nemotron_h() -> ServingFamily:
    """jamba's two memories (a state-space state by the slot beside
    pages) WITH experts, held in part as kimi_k2's are: the state a
    matrix a head (Mamba-2), the experts in a latent narrower than the
    model. `serve/` and `PagePool` needed nothing for it."""
    from ray_tpu.models import nemotron_h

    return _paged_only(
        nemotron_h,
        "the Mamba-2 layers' state-space state and convolution tail "
        "(models/nemotron_h.py: a float32 matrix of 64 x 128 a head and "
        "layer, two heads side by side, 21.0 MB a slot at the published "
        "sizes, indexed by slot)",
        {**_EXPERTS,
         "prefix_cache": _SNAPSHOT,
         "spec_draft": "a recurrence cannot be run backwards; " + _RETURNS
                       + " (the model's own multi-token-prediction head "
                       "would be the draft)",
         "tp": "2 KV heads cannot shard over more chips than heads, "
               + _EXCHANGE + ", and no partition rule splits the "
               "state-space state by head",
         "kv_dtype": "which the attention layers' K/V writer would have to "
                     "call, and the state-space state is float32: it "
                     "accumulates thousands of steps"},
        slot_state=nemotron_h.SLOT_STATE_LEAVES)


_FAMILIES = {"gpt": _gpt, "zaya": _zaya, "laguna": _laguna,
             "qwen3_next": _qwen3_next, "mimo_v2": _mimo_v2,
             "jamba": _jamba, "kimi_k2": _kimi_k2,
             "olmo_hybrid": _olmo_hybrid, "nemotron_h": _nemotron_h}


@functools.cache
def _family(name: str) -> ServingFamily:
    if name not in _FAMILIES:
        raise ValueError(f"no serving family {name!r}; have "
                         f"{sorted(_FAMILIES)}")
    return _FAMILIES[name]()


def family_of(cfg) -> ServingFamily:
    """The family of a configuration object (its class's `family`
    attribute; "gpt" when it names none)."""
    return _family(getattr(type(cfg), "family", "gpt"))
