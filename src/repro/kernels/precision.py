"""Matmul precision of every fp32 GEMM in the cdist form.

``|a|^2 + |b|^2 - 2 a.b`` cancels: a TPU's default precision rounds fp32
operands to bf16, after which a word's distance to itself is no longer
~0, and on a v5e at the paper's corpus size (w=300) the WMDs moved by up
to 1.4e-2 relative. The jnp paths take this through
``repro.core.sinkhorn.sq_dists``; the Pallas kernels and their oracles
import it directly. Imports only jax, so core and kernels can both
depend on it.
"""
from jax import lax

FP32_GEMM = lax.Precision.HIGHEST
