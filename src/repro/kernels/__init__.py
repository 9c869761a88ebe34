"""Pallas TPU kernels for the analyzer's compute hot spots (DESIGN.md §5).

  binstats  fused timestamp-binning + per-bin moments (scatter-as-matmul)
  histbin   fused binning + log-bucket quantile-sketch histogram (double
            one-hot scatter-as-matmul; feeds reducers.QuantileSketch)
  iqr       in-VMEM bitonic sort + quantiles + Tukey fences
  rolling   rolling mean/std with overlapped block views

Each ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper
with use_kernel/interpret switches; ``interpret`` is a required keyword, so
no caller gets the interpreter without asking) and ref.py (pure-jnp
oracle). All are validated in interpret mode on CPU only. None is on the
trace-analysis path (``repro.core`` imports none of them), and Mosaic
refuses ``binstats`` and ``histbin`` for TPU v5e: the ``valid[:, None]``
broadcast is an unsupported shape cast (vector<1024xi1> ->
vector<1024x1xi1>).
"""
from .binstats import binstats, binstats_ref
from .histbin import histbin, histbin_ref
from .iqr import iqr_fences, iqr_ref
from .rolling import rolling_stats, rolling_ref
from .ssd import ssd_fused, ssd_ref
from .flashattn import flash_attention, flash_attention_ref
