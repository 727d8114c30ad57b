from repro.kernels.spmm.halo_pull import (BLOCK_ROWS, STREAM_CHUNK_ROWS,
                                          halo_spmm_pallas,
                                          halo_spmm_skip_pallas,
                                          halo_spmm_stream_pallas)
from repro.kernels.spmm.ops import (BACKENDS, RESIDENT_STRIPE_MAX_BYTES,
                                    halo_gather, halo_spmm,
                                    halo_spmm_gather, resolve_backend,
                                    select_kernel, spmm, vma_checkable)
from repro.kernels.spmm.ref import (halo_spmm_ref, halo_spmm_skip_ref,
                                    spmm_ref)

__all__ = ["spmm", "spmm_ref", "BLOCK_ROWS", "BACKENDS",
           "halo_gather", "halo_spmm", "halo_spmm_gather", "halo_spmm_ref",
           "halo_spmm_pallas",
           "halo_spmm_skip_pallas", "halo_spmm_skip_ref",
           "halo_spmm_stream_pallas", "resolve_backend", "select_kernel",
           "STREAM_CHUNK_ROWS", "RESIDENT_STRIPE_MAX_BYTES",
           "vma_checkable"]
