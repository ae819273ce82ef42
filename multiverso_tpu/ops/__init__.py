"""Pallas TPU kernels for hot ops (SURVEY.md §8 hard-part #1: LightLDA's
sampler throughput is the risk buffer XLA alone doesn't cover) plus the
server-side table kernel engine (``table_kernels``: KV probe/lookup and
row/COO gather-scatter behind the ``MVTPU_KERNELS`` selection layer)."""

from multiverso_tpu.ops.lda_sampler import (
    gibbs_sample_docblock, gibbs_sample_docblock_build)
from multiverso_tpu.ops.table_kernels import (interpret_mode, kernel_mode,
                                              select_kernel)

__all__ = ["gibbs_sample_docblock", "gibbs_sample_docblock_build",
           "interpret_mode", "kernel_mode", "select_kernel"]
