"""Pallas TPU kernels for the framework's hot ops.

Kernels follow the playbook in the TPU Pallas guide: VMEM-resident blocks,
MXU-aligned tiles (128), sequential grid with scratch accumulators, and
interpret mode on CPU so the same kernels run in the test mesh.

The kernels, by the names a profile shows:

- ``flash_attention.py``: ``dcp_flash_fwd``, ``dcp_flash_fwd_band``,
  ``dcp_flash_bwd_dq``, ``dcp_flash_bwd_dkv``;
- ``cache_update.py``: ``dcp_cache_write``, ``dcp_kv_write``,
  ``dcp_kv_rows_write``, ``dcp_kv_pool_write``;
- ``decode_attention.py``: ``dcp_paged_decode_attn``,
  ``dcp_paged_latent_decode_attn``;
- ``kda_scan.py``: ``dcp_kda_chunk_scan`` (the KDA recurrence over a
  window, a head's state in VMEM from chunk to chunk);
- ``kda_step.py``: ``dcp_kda_step`` (the KDA state's one-token step: a
  live row's state read once and written once, a parked row's not at all;
  chosen by ``ops/attention.py::kda_step_live`` where
  ``_kda_kernel_ok`` holds: one TPU, no mesh, heads of whole lane tiles);
- ``fused_adamw.py``: the fused AdamW update.
"""

from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
    flash_attention)

__all__ = ["flash_attention"]
