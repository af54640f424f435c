"""The comparison with the plain reference that decides ``correct``.

Training (every cell), over the window run's steps 0-2 as the program ran
them:

- ``row_grad_median_gap``: over every table row that step 0 touches, the
  gap between the row's first-gradient norm, as the program's row-wise
  Adagrad state holds it after one step, and the reference's, over the
  reference's; the median row's gap.  It is the number a lower precision
  fails: every row moves with it, while a ReLU input within rounding of
  zero, which the program and the reference (both at ``highest`` but fused
  differently) may put on different sides of the kink, moves only the rows
  of the one sample that holds it;
- ``grad_norm_gap``: the worst leaf's gap between the program's first
  gradient norm, worked out from its optimizer state after one step, and the
  reference's, over the larger of that leaf's reference norm and the median
  leaf's;
- ``update_norm_gap``: the same for the norm of each leaf's change after the
  three steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).

The two worst-leaf numbers hold against faults (a state left unchanged,
half a batch): a kink on one seed in a few moves them as far as a lower
precision does.  The losses' gap is reported beside them, not compared.

What the window itself did, exactly (limit 0):

- ``events_gap``: saves and failures missing from, or added to, the window
  against the paper's policy for the span;
- ``selection_mismatch`` (cpr-mfu): rows a priority save stored that the
  reference's access counts and segment-wise top-k would not, and the
  reverse, over every save of the run;
- ``reservoir_mismatch`` (cpr-ssu): rows a priority save stored that no
  sub-sampled access since the last save named, rows missing where the
  reservoir never overflowed, and slots short where it did;
- ``restore_mismatch``: after each partial restore, rows outside the
  failed shards that changed, and rows inside them, never saved, that are
  not back at their initial value and accumulator; after each full-recovery
  restore, which replays from the last full save and so hands back the
  state the failure found, the rows of every table and accumulator on the
  device that differ from what they were before the restore's fetch;
- ``full_save_bytes_gap`` (full): bytes of a full save other than every
  table, accumulator and dense layer;
- ``full_image_mismatch`` (full): after each full save, rows of the
  store's image that differ from the device state the save was given, over
  ``harness.IMAGE_SAMPLES`` rows of each table and accumulator drawn from
  the seed among the rows training moves, and trainer arrays that differ;
  after the window, tables and accumulators of the image whose digest
  differs from the device state's at the last save.
"""
from __future__ import annotations

import numpy as np

MOVED_FLOOR = 1e-3       # of the median leaf's reference gradient norm


def _median(xs):
    return float(np.median(np.asarray(list(xs), np.float64)))


def training_gaps(prog, ref):
    """``prog``/``ref``: {"losses": [...], "grad_norms": {leaf: v},
    "change_norms": {leaf: v}, "row_norms": [per-table array]} ->
    (numbers, worst leaves and the losses' gap)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad_norms"]
    med_g = _median(g_ref.values())

    def gaps(p, r, leaves, med):
        return {k: abs(p[k] - r[k]) / max(r[k], med) for k in leaves}

    g = gaps(prog["grad_norms"], g_ref, g_ref, med_g)
    moved = [k for k, v in g_ref.items() if v >= MOVED_FLOOR * med_g]
    c_ref = ref["change_norms"]
    u = gaps(prog["change_norms"], c_ref, moved, _median(c_ref[k]
                                                          for k in moved))
    g_leaf, u_leaf = max(g, key=g.get), max(u, key=u.get)
    p_rows = np.concatenate(prog["row_norms"]).astype(np.float64)
    r_rows = np.concatenate(ref["row_norms"]).astype(np.float64)
    live = r_rows > 0
    row_gap = np.abs(p_rows[live] - r_rows[live]) / r_rows[live]
    return ({"row_grad_median_gap": float(np.median(row_gap)),
             "grad_norm_gap": g[g_leaf], "update_norm_gap": u[u_leaf]},
            {"grad_norm_gap": g_leaf, "update_norm_gap": u_leaf,
             "loss_gap": loss_gap,
             "rows_compared": int(live.sum()),
             "row_grad_worst_gap": float(row_gap.max())})


def big_tables(sizes, coverage=0.99):
    """The largest tables until they cover ``coverage`` of all rows (6 of
    the 26 Criteo Kaggle tables), largest first, ties to the lower index."""
    order = sorted(range(len(sizes)), key=lambda t: (-sizes[t], t))
    total, cum, out = sum(sizes), 0, []
    for t in order:
        if cum / total >= coverage:
            break
        out.append(t)
        cum += sizes[t]
    return out


def _ids(cell, t, steps):
    """Table ``t``'s ids fed at ``steps``, every slot of every sample in the
    order the step flattens them (duplicates kept, as the counts take
    them)."""
    B, P = cell.batch, cell.n_train_batches
    sp = cell.pool["sparse"]
    return np.concatenate([sp[(j % P) * B:(j % P + 1) * B, t].reshape(-1)
                           for j in steps]) if len(steps) else \
        np.zeros(0, np.int32)


def _rows_by_save(probes):
    out = {}
    for e, t, rows in probes.saved_rows:
        out.setdefault((e, t), []).append(rows)
    return {k: np.unique(np.concatenate(v)) for k, v in out.items()}


def _intervals(probes):
    prev = -1
    for e, rec in enumerate(probes.saves):
        yield e, range(prev + 1, rec["step"] + 1)
        prev = rec["step"]


SAMPLE_SEGMENTS = 1024   # segments per table the selection check follows
SAMPLE_ROWS = 4096       # never-saved rows per table and restore checked


def sample(rng, n, k):
    """Sorted sample of ``k`` of ``range(n)`` (all of them if fewer)."""
    if n <= k:
        return np.arange(n)
    return np.sort(rng.choice(n, size=k, replace=False))


def selection_mismatch(cell, probes, ref):
    """Segment-wise top-k picks are independent per segment, so the check
    follows a sample of segments per table, drawn from the seed, through
    every save (their counts, picks and clears), plus every table's total
    of picked rows."""
    tr, sizes = cell.traffic, cell.cfg["table_sizes"]
    rng = np.random.default_rng([cell.seed, 0x5E1])
    got = _rows_by_save(probes)
    big = big_tables(sizes)
    plan = {}
    for t in big:
        n = sizes[t]
        rn = max(1, int(tr["r"] * n))
        seg = min(tr["seg_size"], n)
        n_seg = -(-n // seg)
        k = max(1, min(-(-rn // n_seg), seg))
        segs = sample(rng, n_seg, SAMPLE_SEGMENTS)
        local = np.full(n_seg, -1, np.int64)
        local[segs] = np.arange(segs.size)
        rows = (segs[:, None] * seg + np.arange(seg)).ravel()
        n_want = (n_seg - 1) * k + min(k, n - (n_seg - 1) * seg)
        plan[t] = (seg, k, local, rows, rows < n, n_want,
                   np.zeros(rows.size, np.int64))
    bad = 0
    for e, steps in _intervals(probes):
        for t in big:
            seg, k, local, rows, live, n_want, counts = plan[t]
            ids = _ids(cell, t, steps).astype(np.int64)
            ids = ids[local[ids // seg] >= 0]
            np.add.at(counts, local[ids // seg] * seg + ids % seg, 1)
            # rows past the table's end count -1: they never outrank a row
            pick = ref.segment_topk(np.where(live, counts, -1), k, seg)
            pick = pick[live[pick]]
            counts[pick] = 0
            want = rows[pick]
            have = got.get((e, t), np.zeros(0, np.int64))
            have_s = have[local[have // seg] >= 0]
            if not np.array_equal(have_s, want):
                bad += int(np.setxor1d(have_s, want).size)
            bad += abs(int(have.size) - n_want)
    partial = {t for (_, t), rows in got.items() if rows.size < sizes[t]}
    bad += len(partial ^ set(big))
    return bad


def reservoir_mismatch(cell, probes):
    tr, sizes = cell.traffic, cell.cfg["table_sizes"]
    got = _rows_by_save(probes)
    big = big_tables(sizes)
    period = int(tr["ssu_period"])
    bad = 0
    for e, steps in _intervals(probes):
        for t in big:
            rn = max(1, int(tr["r"] * sizes[t]))
            cand = np.concatenate([_ids(cell, t, [j])[::period]
                                   for j in steps]) if len(steps) else \
                np.zeros(0, np.int32)
            want = np.unique(cand)
            have = got.get((e, t), np.zeros(0, np.int64))
            if want.size <= rn:
                bad += int(np.setxor1d(have, want).size)
            else:
                bad += int(np.setdiff1d(have, want).size) + abs(
                    int(have.size) - rn)
    return bad


def _slices_outside(n, ranges):
    edges = [0]
    for lo, hi in sorted(ranges):
        edges += [lo, hi]
    edges.append(n)
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def rows_differ(a, b):
    if np.array_equal(a, b):
        return 0
    d = a != b
    return int(np.count_nonzero(d.reshape(len(d), -1).any(axis=1)))


def _rows_far(a, b, tol):
    if a.size == 0:
        return 0
    far = np.abs(a - b) > tol
    return int(np.count_nonzero(far.reshape(len(far), -1).any(axis=1)))


INIT_TOL = 1e-5          # of a table's init bound 1/sqrt(rows)


def restore_picks(cell, probes, ref):
    """Per failure and table: the never-saved rows of the failed shards that
    the check compares with their initial values, a sample drawn from the
    seed; and per table the union of them, whose initial values the
    reference reads."""
    sizes = cell.cfg["table_sizes"]
    n_emb = int(cell.traffic["system"]["N_emb"])
    rng = np.random.default_rng([cell.seed, 0x8E5])
    picks = []
    for f in probes.failure_states:
        shards = sorted({int(j) % n_emb for j in f["shards"]})
        per = []
        for t, n in enumerate(sizes):
            unsaved = np.zeros(n, bool)
            for j in shards:
                lo, hi = ref.shard_range(n, j, n_emb)
                unsaved[lo:hi] = True
            for e, tt, rows in probes.saved_rows:
                if tt == t and e < f["save_index"]:
                    unsaved[rows[(rows >= 0) & (rows < n)]] = False
            ids = np.flatnonzero(unsaved)
            per.append((ids, ids[sample(rng, ids.size, SAMPLE_ROWS)]))
        picks.append((shards, per))
    union = [np.unique(np.concatenate([p[t][1] for _, p in picks]))
             if picks else np.zeros(0, np.int64) for t in range(len(sizes))]
    return picks, union


def restore_mismatch(cell, probes, ref, picks, union, init_values,
                     detail=None):
    """Rows outside the failed shards must come back bit for bit; rows
    inside them that no save reached must be back at a zero accumulator and,
    for the sampled ones, at their initial values (within ``INIT_TOL`` of the
    init bound: the reference makes the initial weights in its own program,
    whose rounding may differ in the last bit)."""
    sizes = cell.cfg["table_sizes"]
    n_emb = int(cell.traffic["system"]["N_emb"])
    d = {"outside_rows": 0, "outside_accs": 0, "unsaved_rows": 0,
         "unsaved_accs": 0, "unsaved_sampled": 0}
    for f, (shards, per) in zip(probes.failure_states, picks):
        (old_t, old_a), (new_t, new_a) = f["old"], f["new"]
        for t, n in enumerate(sizes):
            ranges = [ref.shard_range(n, j, n_emb) for j in shards]
            for a, b in _slices_outside(n, ranges):
                d["outside_rows"] += rows_differ(new_t[t][a:b], old_t[t][a:b])
                d["outside_accs"] += rows_differ(new_a[t][a:b], old_a[t][a:b])
            unsaved, rows = per[t]
            d["unsaved_accs"] += int(np.count_nonzero(new_a[t][unsaved]))
            want = init_values[t][np.searchsorted(union[t], rows)]
            d["unsaved_sampled"] += int(rows.size)
            d["unsaved_rows"] += _rows_far(new_t[t][rows], want,
                                           INIT_TOL / np.sqrt(n))
    if detail is not None:
        detail.update(d)
    return sum(v for k, v in d.items() if k != "unsaved_sampled")


def full_restore_mismatch(probes):
    """Rows a full-recovery restore changed; a failure whose restored state
    was never read counts one."""
    return sum(f.get("rows_changed", 1) for f in probes.failures)


def full_image_mismatch(probes, arrays_differ):
    """Image rows and trainer arrays unlike the device state, over every
    save, plus the image's arrays unlike the last save's state
    (``arrays_differ``); a reading that is missing counts one."""
    bad = sum(r.get("image_rows_differ", 1) for r in probes.saves
              if not r.get("skipped"))
    return bad + (1 if arrays_differ is None else arrays_differ)


def full_save_bytes_gap(cell, probes, ref):
    want = ref.full_save_bytes(ref.Arch.of(cell.cfg))
    return sum(abs(r["bytes"] - want) for r in probes.saves)


def events_gap(probes, expected):
    n_saves, n_failures = expected
    return (abs(len(probes.window_events("saves")) - n_saves) +
            abs(len(probes.window_events("failures")) - n_failures))
