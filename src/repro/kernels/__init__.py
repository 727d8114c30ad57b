"""Pallas TPU kernels for DIGEST's compute hot-spots.

* ``spmm``: blocked ELL neighbor aggregation — the P_in·H / P_out·H̃ product
  of Eq. 5 (the per-layer hotspot the paper's GPU implementation spends its
  compute on).
* ``flash_attention``: blocked online-softmax attention — the prefill
  hotspot of the assigned transformer architectures.
* ``gat_edge``: fused GAT edge-softmax + aggregation emitting online-
  softmax partials that merge exactly across DIGEST's in-subgraph /
  stale-out-of-subgraph edge split.
"""
from repro.kernels.spmm import halo_spmm, spmm, spmm_ref
from repro.kernels.flash_attention import (attention_ref,
                                           flash_attention_pallas,
                                           multi_head_attention)
from repro.kernels.gat_edge import (gat_aggregate, gat_edge_partial_pallas,
                                    gat_edge_partial_ref, merge_partials)

__all__ = ["spmm", "halo_spmm", "spmm_ref", "attention_ref",
           "flash_attention_pallas", "multi_head_attention",
           "gat_aggregate", "gat_edge_partial_pallas",
           "gat_edge_partial_ref", "merge_partials"]
