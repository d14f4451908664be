"""Pallas TPU kernels, each with ``kernel.py`` (pallas_call + BlockSpec),
``ops.py`` (jit wrapper with an XLA path) and ``ref.py`` (pure-jnp oracle):

* ``expand``     — fused frontier expansion: neighbor-row DMA gather + the
  distance reduction, the search loop's hot path (opt-in through
  ``SearchConfig.use_expand_kernel``). Compiles for TPU v5e (f32 and int8).
* ``rangescan``  — tiled exact range scan (fused MXU distance + in-range
  count + bounded top-K collect).
* ``gatherdist`` — scalar-prefetch row gather + fused distance.
* ``flashattn``  — flash attention fwd with GQA, sliding window, soft-cap.

Only ``expand`` compiles for the chip and sits on a served path. The TPU
compiler refuses ``gatherdist`` (its ``(1, d)`` row blocks) and
``rangescan`` (``failed to legalize operation 'scf.for'``), and
``rerank_fetch`` (``(1, 16)`` output block); they run in interpret mode
only, and no served path calls them. Every wrapper defaults to
``interpret=False``: CPU tests ask for ``interpret=True``.
"""
from .expand import expand_frontier, expand_frontier_ref
from .flashattn import flash_attention, flash_attention_ref
from .gatherdist import gatherdist, gatherdist_ref
from .rangescan import rangescan, rangescan_ref

__all__ = [
    "expand_frontier", "expand_frontier_ref",
    "flash_attention", "flash_attention_ref",
    "gatherdist", "gatherdist_ref",
    "rangescan", "rangescan_ref",
]
