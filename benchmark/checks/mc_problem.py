"""The multicut problem and solution a job wrote, stage by stage, against
plain numpy given the job's own watershed (which ``ws_partition`` checks):

* ``mc_graph_mismatch``: edges of the region adjacency graph (6-neighbour
  faces between different non-zero fragments over the ROI) missing on
  either side, over the reference's edge count; exact.
* ``mc_feature_gap``: the widest gap of an edge's mean boundary value (both
  voxels of every face sampled); an edge whose sample count differs reads
  as 1.
* ``mc_cost_gap``: the widest gap of an edge's cost,
  ``log((1 - p) / p) + log((1 - beta) / beta)`` with ``p`` the mean
  clipped to [0.001, 0.999].
* ``mc_attractive_pairs``: pairs of adjacent final segments whose summed
  cost is positive, which greedy additive contraction never leaves, plus
  fragments the written segmentation splits or invents; exact.
* ``mc_objective_gap``: the multicut objective (the summed reference cost
  of the edges the written segmentation cuts) above that of ``gaec`` run on
  the reference's graph and costs, over the latter's magnitude.  A solution
  that merges too much, or too little, reads high: merging everything cuts
  nothing and reads 1.

``FAULTS`` plant the faults a multicut job can have in a solution (the
program's, or the reference's put in its place): a solver that merges
every fragment, one that merges none, a graph that loses every third edge.
"""

import heapq
import os

import numpy as np

from benchmark.harness import n5

TOL = 1e-9  # summation-order slack of a summed cost
CHECK_WS = "ws_partition"  # the reference watershed, block by block


def _roi(job):
    return tuple(slice(b, e) for b, e in zip(job["begin"], job["end"]))


def _costs(mean, beta):
    p = np.clip(mean.astype(np.float64), 0.001, 0.999)
    return np.log((1.0 - p) / p) + np.log((1.0 - beta) / beta)


def rag(ws: np.ndarray, raw: np.ndarray):
    """``(pairs [m, 2] sorted, sample counts, mean values)`` over the
    6-neighbour faces of ``ws``."""
    us, vs, ss = [], [], []
    for axis in range(ws.ndim):
        a = np.moveaxis(ws, axis, 0)
        r = np.moveaxis(raw, axis, 0)
        lo, hi = a[:-1].ravel(), a[1:].ravel()
        sel = (lo != hi) & (lo != 0) & (hi != 0)
        u, v = np.minimum(lo[sel], hi[sel]), np.maximum(lo[sel], hi[sel])
        rl, rh = r[:-1].ravel()[sel], r[1:].ravel()[sel]
        us += [u, u]
        vs += [v, v]
        ss += [rl, rh]
    u = np.concatenate(us).astype(np.uint64)
    v = np.concatenate(vs).astype(np.uint64)
    s = np.concatenate(ss).astype(np.float64)
    pairs, inv = np.unique(np.stack([u, v], axis=1), axis=0,
                           return_inverse=True)
    inv = inv.ravel()
    counts = np.bincount(inv, minlength=len(pairs)).astype(np.float64)
    sums = np.bincount(inv, weights=s, minlength=len(pairs))
    return pairs, counts, sums / np.maximum(counts, 1)


def _segments_of(ws, seg):
    """Fragment -> segment of the written volumes, and the number of
    fragments split over several segments or segments on background."""
    fg = ws > 0
    bad = int(np.count_nonzero(seg[~fg]))
    pairs = np.unique(np.stack([ws[fg], seg[fg]], axis=1).astype(np.uint64),
                      axis=0)
    frags, first = np.unique(pairs[:, 0], return_index=True)
    bad += len(pairs) - len(frags)
    return frags, pairs[first, 1], bad


def program(ctx, job, entry):
    fields = {"job": job["index"]}
    ws = n5.read(ctx.output_path, entry["ws_key"].format(**fields),
                 job["begin"], job["end"]).astype(np.uint64)
    seg = n5.read(ctx.output_path, entry["key"].format(**fields),
                  job["begin"], job["end"]).astype(np.uint64)
    store = os.path.join(job["tmp_folder"], "data.zarr")
    nodes = n5.read_zarr(store, "graph/nodes").astype(np.uint64)
    edges = n5.read_zarr(store, "graph/edges").astype(np.int64)
    feats = n5.read_zarr(store, "features/edges")
    costs = np.load(os.path.join(job["tmp_folder"], "costs.npy"))
    pairs = np.sort(nodes[edges], axis=1)
    frags, segs, bad = _segments_of(ws, seg)
    return {"ws": ws, "pairs": pairs, "counts": feats[:, -1],
            "means": feats[:, 0], "costs": costs,
            "frags": frags, "segs": segs, "bad": bad}


def reference(ctx, job, entry, got):
    raw = ctx.raw[_roi(job)]
    pairs, counts, means = rag(got["ws"], raw)
    beta = float(ctx.task_config("probs_to_costs")["beta"])
    costs = _costs(means, beta)
    frags = np.unique(pairs)
    uv = np.searchsorted(frags, pairs)
    segs = gaec(len(frags), uv, costs)
    return {"pairs": pairs, "counts": counts, "means": means,
            "costs": costs, "objective": objective(uv, segs, costs)}


def objective(uv: np.ndarray, segs: np.ndarray, costs: np.ndarray) -> float:
    """The multicut objective: the summed cost of the edges between
    different segments (positive cost = attractive, so lower is better)."""
    if len(uv) == 0:
        return 0.0
    return float(costs[segs[uv[:, 0]] != segs[uv[:, 1]]].sum())


def gaec(n: int, uv: np.ndarray, costs: np.ndarray) -> np.ndarray:
    """Greedy additive edge contraction: merge the most attractive pair of
    clusters while its summed cost is positive.  Cluster of each node."""
    parent = np.arange(n)

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    adj = [dict() for _ in range(n)]
    for (a, b), c in zip(uv, costs):
        if a != b:
            adj[a][b] = adj[a].get(b, 0.0) + c
            adj[b][a] = adj[b].get(a, 0.0) + c
    heap = [(-c, a, b) for a in range(n) for b, c in adj[a].items() if a < b]
    heapq.heapify(heap)
    while heap:
        negc, a, b = heapq.heappop(heap)
        if find(a) != a or find(b) != b or adj[a].get(b) != -negc:
            continue  # stale
        if -negc <= 0:
            break
        if len(adj[a]) < len(adj[b]):
            a, b = b, a
        parent[b] = a
        del adj[a][b]
        for w, c in adj[b].items():
            if w == a:
                continue
            del adj[w][b]
            adj[a][w] = adj[a].get(w, 0.0) + c
            adj[w][a] = adj[a][w]
            heapq.heappush(heap, (-adj[a][w], min(a, w), max(a, w)))
        adj[b] = {}
    return np.array([find(i) for i in range(n)])


def control(ctx, job, entry, precision="bfloat16"):
    """The reference in the program's place, every value in bfloat16 (with
    ``precision`` "float64": the reference itself, in which ``FAULTS`` are
    planted to read them apart from rounding)."""
    from benchmark.harness import cell as cell_mod

    def rounded(x):
        if precision == "float64":
            return x
        import ml_dtypes

        return x.astype(ml_dtypes.bfloat16).astype(np.float64)

    ws_check = cell_mod.load_module("checks", CHECK_WS)
    bs, shape = ctx.block_shape, ctx.volume_shape
    n_blocks = [-(-n // s) for n, s in zip(shape, bs)]
    ws = np.zeros([e - b for b, e in zip(job["begin"], job["end"])], np.uint64)
    labels = ws_check.labels(ctx, job, "watershed", precision)
    for (b, e), lab in zip(ws_check.blocks(ctx, job), labels):
        # block-local ids made unique as the program's watershed makes them
        bid = np.ravel_multi_index([o // s for o, s in zip(b, bs)], n_blocks)
        lab = lab.astype(np.uint64)
        sl = tuple(slice(o - j, f - j) for o, f, j in zip(b, e, job["begin"]))
        ws[sl] = np.where(lab > 0, lab + np.uint64(bid * int(np.prod(bs))), 0)
    pairs, counts, means = rag(ws, rounded(ctx.raw[_roi(job)]))
    means = rounded(means)
    beta = float(ctx.task_config("probs_to_costs")["beta"])
    costs = rounded(_costs(means, beta))
    frags = np.unique(ws[ws > 0])
    uv = np.searchsorted(frags, pairs)
    return {"ws": ws, "pairs": pairs, "counts": counts, "means": means,
            "costs": costs, "frags": frags,
            "segs": gaec(len(frags), uv, costs), "bad": 0}


def _merge_all(out):
    return dict(out, segs=np.zeros_like(out["segs"]))


def _split_all(out):
    return dict(out, segs=np.arange(len(out["frags"])))


def _drop_edges(out):
    keep = np.arange(len(out["pairs"])) % 3 != 2
    return dict(out, **{k: out[k][keep]
                        for k in ("pairs", "counts", "means", "costs")})


FAULTS = {"merge_all": _merge_all, "split_all": _split_all,
          "drop_edges": _drop_edges}


def numbers(got, want):
    base = int(want["pairs"][:, 0].max(initial=0)) + 1
    key_g = got["pairs"][:, 0] * base + got["pairs"][:, 1]
    key_w = want["pairs"][:, 0] * base + want["pairs"][:, 1]
    common, ig, iw = np.intersect1d(key_g, key_w, return_indices=True)
    graph = (len(key_g) + len(key_w) - 2 * len(common)) / max(len(key_w), 1)
    same_n = got["counts"][ig] == want["counts"][iw]
    feature = np.abs(got["means"][ig] - want["means"][iw])
    feature = float(np.where(same_n, feature, 1.0).max(initial=0.0))
    cost = float(np.abs(got["costs"][ig] - want["costs"][iw]).max(initial=0))
    # summed program cost between adjacent final segments
    seg = dict(zip(got["frags"].tolist(), got["segs"].tolist()))
    su = np.array([seg.get(int(u), -1) for u in got["pairs"][:, 0]])
    sv = np.array([seg.get(int(v), -1) for v in got["pairs"][:, 1]])
    cut = su != sv
    lo, hi = np.minimum(su[cut], sv[cut]), np.maximum(su[cut], sv[cut])
    attract = 0
    if cut.any():
        keys, inv = np.unique(np.stack([lo, hi], axis=1), axis=0,
                              return_inverse=True)
        sums = np.bincount(inv.ravel(), weights=got["costs"][cut],
                           minlength=len(keys))
        attract = int(np.count_nonzero(sums > TOL))
    # the written segmentation's objective on the reference's graph and
    # costs; a fragment the program did not write is a segment of its own
    frags = np.unique(want["pairs"])
    known = np.isin(frags, got["frags"])
    segs = np.where(known, -1, -2 - np.arange(len(frags)))
    segs[known] = np.asarray([seg[int(f)] for f in frags[known]], np.int64)
    mine = objective(np.searchsorted(frags, want["pairs"]), segs,
                     want["costs"])
    gap = (mine - want["objective"]) / max(abs(want["objective"]), 1.0)
    return {"mc_graph_mismatch": float(graph), "mc_feature_gap": feature,
            "mc_cost_gap": cost,
            "mc_attractive_pairs": float(attract + got["bad"]),
            "mc_objective_gap": float(gap)}
