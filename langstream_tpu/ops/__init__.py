"""Pallas TPU kernels for the serving hot ops.

- :mod:`langstream_tpu.ops.flash_attention` — blocked causal GQA attention
  (prefill/forward): O(S) memory instead of the O(S²) score matrix.
- :mod:`langstream_tpu.ops.paged_attention` — paged decode reads (bf16 and
  int8 pools) and the multi-query history read of continuation/verify.
- :mod:`langstream_tpu.ops.ssm_state` — one decode step of a Mamba-2
  layer's recurrent state, on the stacked state in place: one pass over it
  where the XLA expression makes three.
- :mod:`langstream_tpu.ops.delta_state`, :mod:`langstream_tpu.ops.delta_chunk`
  — the gated delta rule's state: a decode step's pass over it in place, and
  a prefill's chunked rule as one kernel a layer, the state carried through
  a prompt's chunks in VMEM.
- :mod:`langstream_tpu.ops.selfcheck` — builds every kernel above at a
  served model's shapes and compares it with the XLA read it replaces.

On a TPU the kernels run compiled (all four compile on the v5e at
Llama-3-8B shapes — ``chip_smoke.py`` checks it on every run); a selected
kernel that cannot be built raises, nothing falls back to XLA. Interpret
mode is for the CPU tests only and is refused on a TPU backend.
"""

from langstream_tpu.ops.flash_attention import flash_attention  # noqa: F401
