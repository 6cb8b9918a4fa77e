"""Pallas TPU kernels for hot ops (the rebuild's N2/N3 escape hatch)."""

from .flash_attention import (attention, flash_attention, grouped_attention,
                              merge_heads, self_attention, split_heads,
                              xla_attention, xla_grouped_attention)
from .gated_delta import (gated_delta_chunked, gated_delta_recurrence,
                          gated_delta_step)
from .paged_attention import (paged_attn_mode, paged_decode_attention,
                              paged_prefill_attention)

__all__ = ["attention", "flash_attention", "self_attention", "split_heads",
           "merge_heads", "xla_attention", "grouped_attention",
           "xla_grouped_attention",
           "paged_decode_attention", "paged_prefill_attention",
           "paged_attn_mode", "gated_delta_chunked",
           "gated_delta_recurrence", "gated_delta_step"]
