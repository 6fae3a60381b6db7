"""Operations and bytes of one Sinkhorn-WMD solve, from its shapes.

The count follows the algorithm, not the program that implements it, so
every implementation of the same solve reads the same work:

- the gathered tile holds ``E = Q * N * L * B`` K entries (Q queries of
  B words against N docs of L words), whatever the axis order;
- each iteration runs one SDDMM, ``t[n,l] = sum_b K[n,l,b] u[b]``, and
  one SpMM, ``x[n,b] = sum_l K[n,l,b] w[l]``: 2 operations per entry
  each; and the elementwise updates ``u = 1/x`` (Q*N*B) and
  ``w = c/t`` (Q*N*L);
- the distance epilogue runs one more SDDMM and update, then
  ``sum_b u sum_l K*M*w``: 3 operations per entry;
- the compulsory bytes read each K entry once, the doc ELL ``idx`` and
  ``val`` once, ``r`` once, and write the (Q, N) distances once. M is
  not counted: it follows from K as -log(K)/lam.
"""
from __future__ import annotations

from typing import NamedTuple

WORD_BYTES = 4      # float32 values and int32 ids


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other):
        return Work(self.flops + other.flops, self.bytes + other.bytes)


def solve_work(q: int, n: int, length: int, b: int, n_iter: int) -> Work:
    """Work of one solve of Q queries of B words against N docs of L
    words, for ``n_iter`` iterations."""
    e = float(q) * n * length * b
    per_iter = 4 * e + q * n * (b + length)
    epilogue = 2 * e + q * n * length + 3 * e
    flops = n_iter * per_iter + epilogue
    nbytes = WORD_BYTES * (e + 2 * n * length + q * b + q * n)
    return Work(flops, nbytes)


def layout_work(shape: tuple, layout: str, n_iter: int) -> Work:
    """``solve_work`` of a gathered tile given in any axis order, e.g.
    ``(Q, N, L, B)`` as ``"qnlb"`` or ``(Q, B, N, L)`` as ``"qbnl"``."""
    dims = dict(zip(layout, shape))
    return solve_work(dims["q"], dims["n"], dims["l"], dims["b"], n_iter)


def call_work(v_r, nnz_sum: float, n_docs: int, n_iter: int) -> Work:
    """Useful work of one call that solves queries of ``v_r`` words each
    against the same ``n_docs`` docs of ``nnz_sum`` words in all, padding
    excluded: ``solve_work`` summed over queries and ragged docs, with
    the docs' ``idx``/``val`` read once for the call."""
    flops = nbytes = 0.0
    for b in v_r:
        e = float(b) * nnz_sum
        flops += n_iter * (4 * e + n_docs * b + nnz_sum) + 5 * e + nnz_sum
        nbytes += WORD_BYTES * (e + b + n_docs)
    if len(v_r):
        nbytes += WORD_BYTES * 2 * nnz_sum
    return Work(flops, nbytes)


def roofline_seconds(work: Work, peak_flops: float, peak_bw: float):
    """(least seconds, which bound sets it: ``"compute"`` or ``"bytes"``)."""
    tc, tb = work.flops / peak_flops, work.bytes / peak_bw
    return (tc, "compute") if tc >= tb else (tb, "bytes")
