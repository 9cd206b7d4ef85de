"""Device-side ops: Pallas kernels + sequence-parallel attention.

The compute bodies RPC services run between unpack and response framing —
blockwise (flash) attention, ring attention over the ICI ring, the
Ulysses all-to-all variant, and decode attention over a latent cache
(``latent_attention``, the serving lane's Moonlight model). See SURVEY.md §5 (long-context) and §2.8
(parallelism inventory)."""

from brpc_tpu.ops.flash_attention import attention_reference, flash_attention
from brpc_tpu.ops.latent_attention import latent_attention
from brpc_tpu.ops.ring_attention import ring_attention, ulysses_attention

__all__ = [
    "attention_reference", "flash_attention", "latent_attention",
    "ring_attention", "ulysses_attention",
]
