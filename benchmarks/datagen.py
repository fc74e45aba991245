"""Rating data for the training cells: a fixed bipartite graph, seeded values.

The graph (who rated what) is a function of the configuration's ``data``
block alone, NOT of ``--seed``: the program's compiled shapes depend on the
per-user degree sequence (stream chunk boundaries), the per-item degree
sequence (padded block count) and the number of item-id gaps >= 4096 in each
stream chunk (the delta wire's overflow list). A graph drawn from the seed
would recompile every program on every new seed. ``--seed`` draws what rides
on the graph: the planted factors and noise behind the explicit ratings, and
the trainer's own initialisation seed (see ``drivers/train.py``).

Shape of the graph (MovieLens-25M's own, GroupLens 2019): every user has at
least ``min_degree`` ratings, user degrees are shifted-lognormal (median ~71,
mean 153.8, a few tens of thousands at the top), item popularity is a shifted
power law (top item ~81k ratings, median ~6), every item appears, and all
(user, item) pairs are distinct. Items are numbered by descending popularity,
as the templates' preparators number them.

Distinct pairs without a loop over edges: each user draws exactly ``d_u``
items by systematic sampling over its inclusion probabilities
``pi_ui = min(1, c_u * p_i)`` (``sum_i pi_ui = d_u``): points ``theta_u + k``
on the cumulated ``pi`` hit no item twice because no ``pi`` exceeds 1.
"""

from __future__ import annotations

import numpy as np

GRAPH_SEED = 20190101  # fixed: see the module docstring


def user_degrees(n_users: int, n_edges: int, spec: dict) -> np.ndarray:
    """Shifted-lognormal degrees in user order, summing to ``n_edges``."""
    rng = np.random.default_rng(GRAPH_SEED)
    lo = int(spec["min_degree"])
    z = rng.standard_normal(n_users)
    x = np.exp(float(spec["lognormal_mu"]) + float(spec["lognormal_sigma"]) * z)
    spare = n_edges - lo * n_users
    if spare < 0:
        raise ValueError("n_edges is below min_degree * n_users")
    cap = int(spec["max_degree"]) - lo
    a, b = 0.0, 4.0 * spare / x.sum()
    for _ in range(60):  # bisect the scale so the rounded, capped sum fits
        m = 0.5 * (a + b)
        if np.minimum(np.rint(x * m), cap).sum() > spare:
            b = m
        else:
            a = m
    d = np.minimum(np.rint(x * a), cap).astype(np.int64)
    short = spare - int(d.sum())  # >= 0 and small: spread over the largest
    order = np.argsort(-d, kind="stable")
    room = np.nonzero(d[order] < cap)[0][:short]
    d[order[room]] += 1
    if int(d.sum()) != spare:
        raise ValueError("degree sequence cannot reach n_edges under the cap")
    return d + lo


def item_popularity(n_items: int, spec: dict) -> np.ndarray:
    i = np.arange(n_items, dtype=np.float64)
    w = (1.0 + i / float(spec["shift"])) ** (-float(spec["exponent"]))
    return w / w.sum()


def graph(data: dict):
    """``(user_idx int32[E], item_idx int32[E])``, grouped by user, items
    ascending within a user. Same arrays for every seed."""
    n_users, n_items, n_edges = (
        int(data["n_users"]), int(data["n_items"]), int(data["n_edges"])
    )
    deg = user_degrees(n_users, n_edges, data["user_degree"])
    p = item_popularity(n_items, data["item_popularity"])
    P = np.concatenate([[0.0], np.cumsum(p)])  # P[m] = mass of items < m
    P[-1] = 1.0

    # per distinct degree d: m = how many of the hottest items are certain
    # (pi = 1), c = the scale on the rest. m is the least m with
    # (d - m) * p[m] <= 1 - P[m]; the left side falls and the right side's
    # ratio rises with m, so the condition is monotone: vectorised bisection.
    dv = np.unique(deg)
    lo = np.zeros(len(dv), np.int64)
    hi = np.minimum(dv, n_items - 1)
    while np.any(lo < hi):
        mid = (lo + hi) // 2
        ok = (dv - mid) * p[mid] <= 1.0 - P[mid]
        hi = np.where(ok, mid, hi)
        lo = np.where(ok, lo, mid + 1)
    m_of = lo
    c_of = (dv - m_of) / (1.0 - P[m_of])
    slot = np.searchsorted(dv, deg)
    m_u, c_u = m_of[slot], c_of[slot]

    rng = np.random.default_rng(GRAPH_SEED + 1)
    theta = rng.random(n_users)
    start = np.zeros(n_users + 1, np.int64)
    np.cumsum(deg, out=start[1:])
    user_idx = np.empty(n_edges, np.int32)
    item_idx = np.empty(n_edges, np.int32)
    # spans of whole users, ~1M edges each: temporaries stay small enough
    # for the allocator to reuse (fresh pages are slow on these hosts)
    cuts = np.searchsorted(start, np.arange(0, n_edges, 1 << 20))
    cuts = np.unique(np.append(cuts, n_users))
    with np.errstate(divide="ignore", invalid="ignore"):
        for u0, u1 in zip(cuts[:-1], cuts[1:]):
            e0, e1 = int(start[u0]), int(start[u1])
            u = np.repeat(np.arange(u0, u1, dtype=np.int32), deg[u0:u1])
            k = np.arange(e0, e1, dtype=np.int64) - start[u]
            m_e = m_u[u]
            # beyond the certain prefix: point (k - m) + theta on the tail
            t = (k - m_e + theta[u]) / c_u[u] + P[m_e]
            tail = np.searchsorted(P, t, side="right") - 1
            np.clip(tail, 0, n_items - 1, out=tail)
            user_idx[e0:e1] = u
            item_idx[e0:e1] = np.where(k < m_e, k, tail)

    # float rounding can land two neighbouring points on one item: push the
    # later one up (items ascend within a user, so duplicates are adjacent)
    for _ in range(8):
        dup = np.nonzero(
            (item_idx[1:] <= item_idx[:-1]) & (user_idx[1:] == user_idx[:-1])
        )[0] + 1
        if not len(dup):
            break
        item_idx[dup] = item_idx[dup - 1] + 1
    else:
        raise ValueError("could not make the pairs distinct")
    if int(item_idx.max()) >= n_items:
        raise ValueError("duplicate repair ran off the catalogue")

    # every item appears: hand each absent item one edge that pointed at one
    # of the hottest items (which keep tens of thousands)
    absent = np.nonzero(np.bincount(item_idx, minlength=n_items) == 0)[0]
    if len(absent):
        donors = np.nonzero(item_idx < int(data["item_popularity"]["donor_items"]))[0]
        take = rng.choice(donors, size=len(absent), replace=False)
        item_idx[take] = absent.astype(np.int32)
    return user_idx, item_idx


def planted_ratings(user_idx, item_idx, n_users, n_items, spec, seed):
    """Half-star ratings from a planted low-rank model plus noise, so that a
    trained model fits far better than a constant: mean + x_u.y_i + noise,
    snapped to the 0.5 grid and clipped to [0.5, 5.0]."""
    rng = np.random.default_rng([int(seed), 1])
    r0 = int(spec["planted_rank"])
    scale = np.float32((float(spec["signal_std"]) ** 2 / r0) ** 0.25)
    X = rng.standard_normal((n_users, r0), np.float32) * scale
    Y = rng.standard_normal((n_items, r0), np.float32) * scale
    out = np.empty(len(user_idx), np.float32)
    step = 1 << 20  # small temporaries: see graph()
    for e0 in range(0, len(user_idx), step):
        s = slice(e0, e0 + step)
        dot = np.einsum("ek,ek->e", X[user_idx[s]], Y[item_idx[s]])
        noise = rng.standard_normal(len(dot), np.float32)
        out[s] = float(spec["mean"]) + dot + np.float32(spec["noise_std"]) * noise
    out *= np.float32(2.0)
    np.rint(out, out=out)
    np.clip(out, 1.0, 10.0, out=out)
    out *= np.float32(0.5)
    return out


def ratings(user_idx, item_idx, data: dict, seed: int) -> np.ndarray:
    spec = data["ratings"]
    if spec["kind"] == "ones":
        return np.ones(len(user_idx), np.float32)
    if spec["kind"] == "planted":
        return planted_ratings(
            user_idx, item_idx, int(data["n_users"]), int(data["n_items"]),
            spec, seed,
        )
    raise ValueError(f"unknown ratings kind {spec['kind']!r}")
