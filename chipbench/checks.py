"""What decides ``correct``: exact checks of what generation and the
feature fetch produced, and the comparison of the training steps with
the plain reference (``reference.py``).

Every function returns numbers; ``verdict`` holds each against its limit.
"""
from __future__ import annotations

import numpy as np


def has_edges(indptr, indices, parents, children) -> np.ndarray:
    """Whether each ``parents[i] -> children[i]`` is an edge of the CSR
    (neighbour lists sorted): a binary search within each row."""
    lo = indptr[parents].astype(np.int64)
    end = indptr[parents + 1].astype(np.int64)
    hi = end.copy()
    while True:
        active = lo < hi
        if not active.any():
            break
        mid = (lo + hi) // 2
        below = indices[np.minimum(mid, len(indices) - 1)] < children
        lo = np.where(active & below, mid + 1, lo)
        hi = np.where(active & ~below, mid, hi)
    hit = indices[np.minimum(lo, len(indices) - 1)] == children
    return (lo < end) & hit


def check_sample(indptr, indices, seeds, hops, masks) -> dict:
    """Generation: each masked-in id is a neighbour of its parent, and
    each mask is exactly "parent masked in and of out-degree > 0" (masks
    chain; a node with neighbours always gets its fanout)."""
    deg = np.diff(indptr)
    parents = np.asarray(seeds).reshape(-1)
    parent_mask = np.ones(parents.shape, bool)
    bad_ids = bad_masks = 0
    for hop, mask in zip(hops, masks):
        hop, mask = np.asarray(hop), np.asarray(mask)
        k = hop.shape[-1]
        p = np.repeat(parents, k)
        c = hop.reshape(-1)
        m = mask.reshape(-1)
        want = np.repeat(parent_mask & (deg[parents] > 0), k)
        bad_masks += int(np.sum(m != want))
        sel = m & want
        bad_ids += int(np.sum(~has_edges(indptr, indices, p[sel], c[sel])))
        parents, parent_mask = c, m
    return {"bad_ids": bad_ids, "bad_masks": bad_masks}


def check_rows(table, labels, seeds, hops, masks, x_seed, x_hops,
               y) -> dict:
    """Feature fetch: every masked-in row is its id's table row, every
    padded row is zero, and the labels are the seeds' (the comparison of
    ``chip_smoke.check_rows``, made on the device).  ``table`` and
    ``labels`` are the benchmark's own, never the program's."""
    import jax
    import jax.numpy as jnp

    def count(table, labels, seeds, hops, masks, x_seed, x_hops, y):
        bad = jnp.sum(jnp.any(x_seed != table[seeds], axis=-1))
        for h, m, x in zip(hops, masks, x_hops):
            want = jnp.where(m[..., None], table[h], 0)
            bad += jnp.sum(jnp.any(x != want, axis=-1))
        return bad, jnp.sum(y != labels[seeds])

    bad, bad_labels = jax.jit(count)(table, labels, seeds, hops, masks,
                                     x_seed, x_hops, y)
    return {"bad_rows": int(bad), "bad_labels": int(bad_labels)}


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's
    (of the leaves in ``keep``, default all)."""
    keep = list(ref) if keep is None else list(keep)
    pn, rn = _norms({k: prog[k] for k in keep}), _norms({k: ref[k]
                                                        for k in keep})
    med = float(np.median(list(rn.values())))
    return max(abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30) for k in keep)


def moved_leaves(ref_grad: dict) -> list:
    """Leaves whose reference gradient is not nought to rounding: norm at
    least a thousandth of the median leaf's.  The others move under Adam
    by round-off alone and are left out of the change."""
    n = _norms(ref_grad)
    med = float(np.median(list(n.values())))
    return [k for k, v in n.items() if v >= 1e-3 * med]


def model_gaps(prog_losses, prog_grad, prog_p0, prog_p3,
               ref_losses, ref_grad, ref_p3) -> dict:
    """The three model numbers: worst relative loss gap over the steps,
    the first clipped gradient's worst leaf gap, and the worst leaf gap
    of the parameters' change over the steps."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog_losses, ref_losses))
    keep = moved_leaves(ref_grad)
    d_prog = {k: np.asarray(prog_p3[k], np.float64) - prog_p0[k]
              for k in keep}
    d_ref = {k: np.asarray(ref_p3[k], np.float64) - prog_p0[k]
             for k in keep}
    return {"loss_gap": float(loss),
            "grad_gap": leaf_gap(prog_grad, ref_grad),
            "update_gap": leaf_gap(d_prog, d_ref)}


def verdict(numbers: dict, limits: dict) -> bool:
    """True where every number with a limit is at or under it.  A number
    that is not finite fails."""
    ok = True
    for name, limit in limits.items():
        v = numbers.get(name)
        if v is None or not np.isfinite(v) or v > limit:
            ok = False
    return ok
