"""The solve's work count reads the same work whatever implements it."""
import itertools

import pytest

from work import Work, call_work, layout_work, roofline_seconds, solve_work


@pytest.mark.parametrize("q,n,length,b", [(4, 1250, 40, 48), (1, 8, 16, 8),
                                          (2, 3, 5, 7)])
def test_einsum_and_kernel_layouts_count_the_same(q, n, length, b):
    einsum = layout_work((q, n, length, b), "qnlb", 15)   # _gather_g default
    kernel = layout_work((q, b, n, length), "qbnl", 15)   # Pallas tile order
    assert einsum == kernel == solve_work(q, n, length, b, 15)
    for perm in itertools.permutations("qnlb"):
        dims = dict(zip("qnlb", (q, n, length, b)))
        assert layout_work(tuple(dims[a] for a in perm), "".join(perm),
                           15) == einsum


def test_hand_computed_case():
    # Q=1, N=2, L=3, B=2, 1 iteration: E = 12 entries
    # per iteration: SDDMM + SpMM 4E = 48, updates Q*N*(B+L) = 10
    # epilogue: SDDMM 2E = 24, update Q*N*L = 6, K*M*w and u-sum 3E = 36
    w = solve_work(1, 2, 3, 2, 1)
    assert w.flops == 48 + 10 + 24 + 6 + 36
    # bytes: K 12, idx+val 2*6, r 2, out 2 words of 4 bytes
    assert w.bytes == 4 * (12 + 12 + 2 + 2)


def test_ragged_call_matches_uniform_solve():
    # docs of equal length: the ragged count is the tile count, with the
    # docs read once for the whole call
    n, length, v_r = 5, 8, [8, 16]
    ragged = call_work(v_r, n * length, n, 15)
    tiles = [solve_work(1, n, length, b, 15) for b in v_r]
    assert ragged.flops == sum(t.flops for t in tiles)
    doc_bytes = 4 * 2 * n * length
    assert ragged.bytes == sum(t.bytes for t in tiles) - doc_bytes


def test_roofline_names_its_bound():
    assert roofline_seconds(Work(197e12, 1.0), 197e12, 819e9) == (1.0,
                                                                  "compute")
    t, bound = roofline_seconds(Work(1.0, 819e9), 197e12, 819e9)
    assert (t, bound) == (1.0, "bytes")
