"""Required training operations against a hand count, and the reference's
check of the partition it is handed."""
import numpy as np
import pytest

from bench import flops, reference


def test_train_flops_hand_count():
    # 2 layers 3 -> 4 -> 2 over 5 real nodes whose Â has 9 non-zeros:
    # forward = 2*5*3*4 + 2*9*4 + 2*5*4*2 + 2*9*2 = 120+72+80+36 = 308
    dims = [(3, 4), (4, 2)]
    assert flops.forward_flops(5, 9, dims) == 308
    assert flops.train_flops(5, 9, dims) == 3 * 308
    # precomputed A'X: the first layer aggregates nothing on the device
    assert flops.forward_flops(5, 9, dims, precompute_ax=True) == 308 - 72


def test_block_nnz_counts_edges_and_the_added_diagonal():
    # path 0-1-2 plus a self loop on 2; batch {0, 1, 2}: 4 edge slots
    # (0-1, 1-0, 1-2, 2-1), the loop on 2, and the diagonal of 0 and 1
    indptr = np.array([0, 1, 3, 5])
    indices = np.array([1, 0, 2, 1, 2])
    g = reference.Graph.from_arrays(indptr, indices, np.ones(5),
                                    np.zeros((3, 1)), np.zeros(3))
    assert reference.block_nnz(g, np.array([0, 1, 2])) == 7
    assert reference.block_nnz(g, np.array([0, 2])) == 2


def _two_cliques():
    # two 3-cliques {0,1,2} and {3,4,5} joined by the edge 2-3
    edges = [(a, b) for c in ((0, 1, 2), (3, 4, 5)) for a in c for b in c
             if a != b] + [(2, 3), (3, 2)]
    rows, cols = zip(*sorted(edges))
    indptr = np.searchsorted(rows, np.arange(7))
    return reference.Graph.from_arrays(indptr, np.array(cols),
                                       np.ones(len(cols)),
                                       np.zeros((6, 1)), np.zeros(6))


def test_partition_chance_ratio_separates_a_clustering_from_chance():
    g = _two_cliques()
    # 12 of 14 edge entries inside a part; chance keeps (1/2)^2 * 2 = 1/2
    n = reference.partition_numbers(g, np.array([0, 0, 0, 1, 1, 1]), 2)
    assert n == {"partition.invalid": 0.0,
                 "partition.chance_ratio": pytest.approx(0.5 / (12 / 14))}
    # an assignment of another graph: 4 of 14 entries inside a part
    n = reference.partition_numbers(g, np.array([0, 1, 0, 1, 0, 1]), 2)
    assert n["partition.chance_ratio"] == pytest.approx(0.5 / (4 / 14))


@pytest.mark.parametrize("parts, invalid", [
    ([0, 0, 0, 1, 1], 1),                 # one node left out
    ([0, 0, 0, 1, 1, 1, 1], 1),           # one assignment too many
    ([0, 0, 0, 1, 1, 2], 1),              # an id out of range
    ([0, 0, 0, 0, 0, -1], 2),             # out of range, and part 1 empty
    ([0, 0, 0, 0, 0, 0], 1),              # part 1 empty
])
def test_partition_that_is_no_assignment_is_invalid(parts, invalid):
    n = reference.partition_numbers(_two_cliques(), np.array(parts), 2)
    assert n["partition.invalid"] == invalid
    assert n["partition.chance_ratio"] == float("inf")
