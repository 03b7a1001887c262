"""TPU kernel ops (Pallas) with XLA fallbacks.

The reference has no custom kernels (SURVEY.md §2: 100% Python/torch); its
hot loop is eager per-batch SGD. Here the hot ops get TPU-native fused
implementations:

- :mod:`fedml_tpu.ops.attention` — blockwise (flash) attention: online
  softmax over K/V blocks, MXU-shaped matmuls, partial (o, m, l) outputs so
  sequence-parallel ring attention can merge chunks across devices.
- :mod:`fedml_tpu.ops.grouped_matmul` — the sparse-expert layer's grouped
  matmul over rows sorted by expert (three kernels that read the experts'
  float32 matrices themselves and cast the tile they hold;
  ``lax.ragged_dot`` off the TPU) and the row moves around it, gathers in
  both directions.
- :mod:`fedml_tpu.ops.kda` — the delta rule with a per-channel decay (a
  linear-attention layer's recurrence) in chunks: a kernel pair, forward
  and backward, that makes a chunk's operands itself and keeps a head's
  state in VMEM from chunk to chunk; the ``jax.numpy`` scan is the ``xla``
  path.
- :mod:`fedml_tpu.ops.xent` — fused masked softmax cross-entropy over large
  vocabularies without materializing log-softmax in HBM.

Every op has an ``impl`` switch: ``'pallas'`` (TPU kernel), ``'xla'``
(pure-jnp, fuses well enough on any backend), ``'auto'`` (pallas on TPU,
xla elsewhere). Tests run both paths and assert parity.
"""

from fedml_tpu.ops.attention import (  # noqa: F401
    attention,
    attention_block_partial,
    merge_partials,
    normalize_partial,
)
from fedml_tpu.ops.xent import masked_cross_entropy  # noqa: F401
