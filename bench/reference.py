"""The plain reference: what the served path must publish for the records
it acked, and the comparison that decides ``correct``.

It imports nothing of the program.  Windows follow the paper's Algorithm 3:
a window closes when the ``nt_w + 1``-th unique timestamp arrives, and the
record carrying it opens the next window.  A window's butterflies are
counted on its distinct edges (the ``distinct`` duplicate policy, keep
first) as ``sum over vertex pairs of C(common neighbours, 2)``.
Estimates follow sGrapp (Algorithm 4), ``B_k = sum_{l<=k} count_l +
sum_{1<=l<=k} |E_l|**alpha0`` with ``|E_l|`` the records seen when
window ``l`` closed, in the precision the estimator publishes: float32, one
window at a time, adding the window's count and then its inter-window term.
"""
from __future__ import annotations

import numpy as np


def window_bounds(tau: np.ndarray, nt_w: int) -> np.ndarray:
    """Start offsets of every window that has begun, given the records of a
    stream in order.  All windows but the last are closed."""
    if tau.shape[0] == 0:
        return np.zeros(0, dtype=np.int64)
    new_ts = np.ones(tau.shape[0], dtype=bool)
    new_ts[1:] = tau[1:] != tau[:-1]
    ordinal = np.cumsum(new_ts) - 1           # unique-timestamp ordinal
    win = ordinal // nt_w
    starts = np.flatnonzero(np.r_[True, win[1:] != win[:-1]])
    return starts.astype(np.int64)


def _local_ranks(w: np.ndarray, v: np.ndarray) -> tuple:
    """Rank of each vertex ``v`` among the distinct vertices of its window
    ``w`` (both sorted by window), and the distinct count of each window."""
    order = np.lexsort((v, w))
    ws, vs = w[order], v[order]
    new = np.r_[True, (ws[1:] != ws[:-1]) | (vs[1:] != vs[:-1])]
    gid = np.cumsum(new) - 1                  # distinct (window, vertex) id
    first_gid = gid[np.r_[True, ws[1:] != ws[:-1]]]
    win_idx = np.cumsum(np.r_[True, ws[1:] != ws[:-1]]) - 1
    rank = np.empty_like(gid)
    rank[order] = gid - first_gid[win_idx]
    n_windows = int(w.max()) + 1 if w.shape[0] else 0
    distinct = np.zeros(n_windows, dtype=np.int64)
    np.add.at(distinct, ws[new], 1)
    return rank, distinct


def window_butterflies(w: np.ndarray, ei: np.ndarray, ej: np.ndarray,
                       n_windows: int, max_wedges: int = 1 << 24) -> tuple:
    """(butterflies, distinct i-vertices, distinct j-vertices) of every
    window ``0..n_windows-1``; record ``r`` lies in window ``w[r]``.

    Each window counts its distinct edges.  Every j-vertex of degree ``d``
    gives ``C(d, 2)`` wedges, one per pair of its i-neighbours; a pair of
    i-vertices with ``m`` common j-neighbours closes ``C(m, 2)``
    butterflies.  The same holds with the sides swapped, so the side with
    fewer wedges is walked.  Windows are taken in runs of at most
    ``max_wedges`` wedges, so memory stays bounded."""
    zero = np.zeros(n_windows, dtype=np.int64)
    if n_windows == 0:
        return zero, zero.copy(), zero.copy()
    ri, n_i = _local_ranks(w, ei)
    rj, n_j = _local_ranks(w, ej)
    # distinct edges, sorted by window
    order = np.lexsort((ri, rj, w))
    w, ri, rj = w[order], ri[order], rj[order]
    keep = np.r_[True, (w[1:] != w[:-1]) | (rj[1:] != rj[:-1])
                 | (ri[1:] != ri[:-1])]
    w, ri, rj = w[keep], ri[keep], rj[keep]
    wedges = []
    for a, b in ((ri, rj), (rj, ri)):        # pairs of a around each b
        o = np.lexsort((a, b, w))
        wa, a, b = w[o], a[o], b[o]
        grp = np.flatnonzero(np.r_[True, (wa[1:] != wa[:-1])
                                   | (b[1:] != b[:-1])])
        size = np.diff(np.r_[grp, wa.shape[0]])
        # wedges each edge opens with the edges after it in its group
        opens = (np.repeat(size, size) - 1
                 - (np.arange(wa.shape[0]) - np.repeat(grp, size)))
        wedges.append((int(opens.sum()), wa, a, opens))
    _, w, a, opens = min(wedges, key=lambda x: x[0])
    per_window = np.bincount(w, weights=opens, minlength=n_windows)
    bits = int(a.max()).bit_length()
    counts = zero
    lo = 0
    while lo < n_windows:
        hi = lo + 1
        total = per_window[lo]
        while hi < n_windows and total + per_window[hi] <= max_wedges:
            total += per_window[hi]
            hi += 1
        e0, e1 = np.searchsorted(w, [lo, hi])
        o = opens[e0:e1]
        first = np.repeat(np.arange(e0, e1), o)
        step = np.arange(first.shape[0]) - np.repeat(np.cumsum(o) - o, o)
        second = first + 1 + step
        key = ((w[first] - lo) << 2 * bits) | (a[first] << bits) | a[second]
        key.sort()
        run = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        m = np.diff(np.r_[run, key.shape[0]])
        counts += np.bincount(lo + (key[run] >> 2 * bits),
                              weights=m * (m - 1) // 2,
                              minlength=n_windows).astype(np.int64)
        lo = hi
    return counts, n_i, n_j


def replay(tau: np.ndarray, ei: np.ndarray, ej: np.ndarray, nt_w: int,
           alpha0: float) -> dict:
    """Every closed window of one tenant's acked records, in order."""
    starts = window_bounds(tau, nt_w)
    n_closed = max(starts.shape[0] - 1, 0)
    n_rec = int(starts[-1]) if n_closed else 0   # records in closed windows
    w = np.repeat(np.arange(n_closed), np.diff(starts)) if n_closed else \
        np.zeros(0, dtype=np.int64)
    counts, n_i, n_j = window_butterflies(w, ei[:n_rec], ej[:n_rec],
                                          n_closed)
    ends = starts[1:]
    cum = ends.astype(np.int64)               # records seen at each close
    end_tau = tau[ends - 1] if n_closed else np.zeros(0)
    est = np.empty(n_closed, dtype=np.float32)
    inter = cum.astype(np.float32) ** np.float32(alpha0)
    acc = np.float32(0.0)
    for k in range(n_closed):
        acc = np.float32(acc + np.float32(counts[k]))
        if k > 0:                             # no inter-window term at k=0
            acc = np.float32(acc + inter[k])
        est[k] = acc
    return {"count": counts, "cum_sgrs": cum, "end_tau": end_tau,
            "estimate": est, "n_i": n_i, "n_j": n_j,
            "close_record": ends.astype(np.int64)}


def compare(published: list[dict], reference: list[dict],
            closed_by_acks: list[int]) -> dict:
    """The numbers that decide ``correct``, per tenant summed or maxed.

    ``published[s]`` holds tenant ``s``'s estimate messages as arrays
    (``window``, ``count``, ``estimate``, ``cum_sgrs``, ``end_tau``);
    ``reference[s]`` is :func:`replay` of its acked records;
    ``closed_by_acks[s]`` sums ``windows_closed`` over its acks.

    - ``windows_wrong``: published windows whose count, ``cum_sgrs`` or
      ``end_tau`` differ from the reference, that the reference never
      closed, or that were published twice.
    - ``windows_missing``: windows the reference closed and no estimate
      reported.
    - ``closed_gap``: |windows the acks say closed - windows the reference
      closed|, summed over tenants: every acked record lies in a counted
      window or in the open one.
    - ``estimate_rel_gap``: the widest ``|published - reference| /
      reference`` over published windows.
    """
    wrong = missing = closed_gap = 0
    gap = 0.0
    for pub, ref, acked_closed in zip(published, reference, closed_by_acks):
        n_ref = ref["count"].shape[0]
        closed_gap += abs(int(acked_closed) - n_ref)
        w = np.asarray(pub["window"], dtype=np.int64)
        uniq, first = np.unique(w, return_index=True)
        wrong += w.shape[0] - uniq.shape[0]
        inside = (w >= 0) & (w < n_ref)
        wrong += int((~inside).sum())
        sel = np.intersect1d(first, np.flatnonzero(inside))
        k = w[sel]
        bad = ((np.asarray(pub["count"])[sel] != ref["count"][k])
               | (np.asarray(pub["cum_sgrs"])[sel] != ref["cum_sgrs"][k])
               | (np.asarray(pub["end_tau"])[sel] != ref["end_tau"][k]))
        wrong += int(bad.sum())
        missing += n_ref - np.unique(k).shape[0]
        if k.shape[0]:
            r = ref["estimate"][k]
            d = np.abs(np.asarray(pub["estimate"], np.float64)[sel] - r)
            gap = max(gap, float((d / np.maximum(np.abs(r), 1.0)).max()))
    return {"windows_wrong": wrong, "windows_missing": missing,
            "closed_gap": closed_gap, "estimate_rel_gap": gap}
