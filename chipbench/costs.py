"""Operations and bytes the algorithm needs, from shapes alone.  A model
family's operations per seed are its own (``models/<family>.py``,
``flops_per_seed``); what every family shares is here.

``gen_min_bytes`` is the least HBM traffic one worker's generation must
move in a step: the CSR reads of every sampled neighbour, the sampled
ids and masks it writes, each distinct feature row read once and every
padded slot's feature row written once.
"""
from __future__ import annotations


def tree_levels(fanouts) -> list:
    """Nodes per seed at each tree level: ``[1, k1, k1*k2, ...]``."""
    levels = [1]
    for k in fanouts:
        levels.append(levels[-1] * k)
    return levels


def gen_min_bytes(fanouts, seeds_per_worker: int, n_workers: int,
                  feat_dim: int, distinct_ids: float,
                  row_bytes: int = 4, id_bytes: int = 4) -> float:
    """Least bytes one worker's generation moves in one step, where
    ``distinct_ids`` is its step's count of distinct requested ids."""
    levels = tree_levels(fanouts)
    total = 0.0
    for level, k in enumerate(fanouts):
        frontier = n_workers * seeds_per_worker * levels[level]
        total += frontier * (2 * id_bytes + k * id_bytes)   # indptr, nbrs
    slots = seeds_per_worker * sum(levels)
    total += slots * (id_bytes + 1)                          # ids, masks
    total += distinct_ids * feat_dim * row_bytes             # rows read
    total += slots * feat_dim * row_bytes                    # rows written
    return total
