"""A program family, as the serving engine asks it.

A family serves its models through programs of its own beside the paged
pool (``hybrid.py``, ``latent.py``, ``swa.py``; the dense family, Llama and
the MoE FFN plugged into it, is still the engine's own arm). Each family's
module ends in ONE ``FAMILY = Family(...)``: what the engine used to decide
by comparing a family's name, it now reads off that record, so a new family
is its module, its tests and its benchmark files, and no edit to
``serving/engine.py``.

Imports nothing of the engine and nothing of a family: the family modules
import this one, and :func:`family_of` imports them by name, the first time
a served name is not the engine's own.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Iterator

#: the modules that end in a ``FAMILY``, in the order they are asked
MODULES = [
    "langstream_tpu.models.hybrid",
    "langstream_tpu.models.latent",
    "langstream_tpu.models.swa",
    "langstream_tpu.models.eva",
]


@dataclasses.dataclass(frozen=True)
class Family:
    name: str                   # in logs and the refusals' text
    config_class: type
    presets: dict               # served name -> classmethod of config_class
    what: str                   # "keeps ...": why some options are refused
    refusals: dict              # option -> this family's reason
    init_params: Callable       # (mc) -> params
    #: (mc, layout, slots) -> (init_cache, init_state or None): two thunks,
    #: the (cache_k, cache_v) pair and what rides behind them in the
    #: engine's ``state``
    init_pools: Callable
    #: (mc, params, residents, tokens, lengths, sel, use_flash=, kernel=)
    #: -> (logits, residents)
    prefill: Callable
    #: (mc, params, residents, tokens, lengths, active, tables, sample_fn,
    #: key, K, num_read_blocks=, kernel=, sample_extras=, return_packed=)
    #: -> (packed, tokens, lengths, *residents)
    decode_chunk: Callable
    #: how many positional arguments after ``params`` stay on the device
    #: from call to call, and which arguments are donated
    residents: int
    donate: tuple
    block_manager_kwargs: Callable = lambda mc, layout, slots: {}
    prefill_compiler_options: Callable = lambda mc, backend: None
    prefill_selects_slots: bool = False  # sel is (tables, slot_ids)
    one_decode_window: bool = False      # max_blocks_per_slot, no buckets
    # what of the ONE kernel selection its programs are handed is reported:
    state_kernels: bool = False   # ssm_state_kernel (its state has kernels)
    expert_kernels: bool = False  # moe_grouped_kernel (its prefill has experts)
    pool_rows: Callable | None = None    # (mc, block_mgr, rows) -> a gauge

    def config(self, name: str, max_seq_len: int) -> Any:
        return getattr(self.config_class, self.presets[name])(
            max_seq_len=max_seq_len)


def families() -> Iterator[Family]:
    """Every module's ``FAMILY``, each module imported as it is reached."""
    for module in MODULES:
        yield importlib.import_module(module).FAMILY


def family_of(name: str) -> Family | None:
    """The family that serves ``name``: modules are imported in turn until
    one's ``presets`` has it."""
    return next((f for f in families() if name in f.presets), None)
