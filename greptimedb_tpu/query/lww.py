"""Last-write-wins over a scan's rows, decided where the rows already are.

A table that is not append-mode keeps, of the rows that share a
(primary key, ts), the one written last, and hides it if that one is a
DELETE (mito2 read/merge.rs, read.rs:59-73). A scan here is a
concatenation of runs — one per SST, each sorted by (tags..., ts, seq)
as `Region._sort_order` wrote it, then the memtable's rows as they
arrived — so which rows repeat is a merge of sorted runs, and the host
holds the runs. `keep_mask` does that merge with numpy:

  * every row gets one int64 that orders it by (primary key, ts): the
    tag codes in mixed radix (NULL, code -1, is digit 0) over
    ts - min(ts) (`series_ids`, then `keep_mask`). Inside a run the
    keys already ascend;
  * rows whose keys ascend strictly cannot repeat: one pass proves it
    (`path="none"`, and no tombstone: no mask at all);
  * else a STABLE argsort of the keys merges the runs (timsort: time
    grows with the rows and the number of runs, not n log n), equal
    neighbours are the repeats, and only those are looked at again to
    find each one's highest sequence (`path="host_merge"`).

Nothing here depends on the row count but array lengths: no program is
compiled, and no scan is sorted on the device. Where 62 bits do not hold
the keys, the runs are merged by the series ids and the timestamps as
two keys; where they do not even hold the tag codes' radix, the series
ids are the dense ranks of the rows' code tuples (`np.unique`), which
order as the tuples do.
"""

from __future__ import annotations


import numpy as np

from greptimedb_tpu.ops.dedup import OP_DELETE

_KEY_BITS = 62


def series_ids(scan, tag_names: list) -> np.ndarray:
    """int64 per row, equal where the primary keys are equal and
    ascending inside a sorted run: the tag codes in mixed radix, first
    tag most significant, or their tuples' dense ranks where 62 bits do
    not hold the radix."""
    n = scan.num_rows
    span = 1
    for t in tag_names:
        span *= len(scan.tag_dicts[t]) + 1
    if span >= 1 << _KEY_BITS:
        if not tag_names:
            return np.zeros(n, dtype=np.int64)
        codes = np.stack([np.asarray(scan.columns[t]) for t in tag_names],
                         axis=1)
        _, sid = np.unique(codes, axis=0, return_inverse=True)
        return sid.reshape(-1).astype(np.int64)
    sid = np.zeros(n, dtype=np.int64)
    for t in tag_names:
        sid *= len(scan.tag_dicts[t]) + 1
        sid += scan.columns[t]
        sid += 1
    return sid


def keep_mask(scan, tag_names: list, ts_name: str) -> tuple:
    """(mask, path, duplicates): `mask[i]` says whether row i of the
    scan survives last-write-wins and tombstones — None where every row
    does; `path` is "none" (the keys ascend strictly: nothing repeats)
    or "host_merge" (the sorted runs were merged here); `duplicates` the
    rows that lost to a later write."""
    n = scan.num_rows
    op = np.asarray(scan.op_type)
    # what survives where nothing repeats: all but the tombstones
    alive = ~(op == OP_DELETE) if op.any() else None
    sid = series_ids(scan, tag_names)
    ts = np.asarray(scan.columns[ts_name], dtype=np.int64)
    if n < 2:
        return alive, "none", 0
    lo, hi = int(ts.min()), int(ts.max())
    width = hi - lo + 1
    top = int(sid.max()) + 1
    if top * width < 1 << _KEY_BITS:
        sid *= width
        sid += ts
        sid -= lo
        keys, second = sid, None
    else:
        keys, second = sid, ts
    if second is None and bool((keys[1:] > keys[:-1]).all()):
        return alive, "none", 0
    if second is None:
        order = np.argsort(keys, kind="stable")
        ks = keys[order]
        same = ks[1:] == ks[:-1]
    else:
        order = np.lexsort((second, keys))
        ks, ts_s = keys[order], second[order]
        same = (ks[1:] == ks[:-1]) & (ts_s[1:] == ts_s[:-1])
    if not same.any():
        return alive, "host_merge", 0
    # the repeats only: rows of a run of equal keys. Among them the
    # highest sequence wins, the later row at equal sequences
    multi = np.r_[same, False] | np.r_[False, same]
    rows = order[multi]
    run = np.cumsum(np.r_[True, ~same])[multi]
    by_seq = np.lexsort((np.asarray(scan.seq)[rows], run))
    rows, run = rows[by_seq], run[by_seq]
    last = np.r_[run[1:] != run[:-1], True]
    mask = np.ones(n, dtype=bool)
    mask[rows] = False
    mask[rows[last]] = True
    if alive is not None:
        mask &= alive
    return mask, "host_merge", int(len(rows) - last.sum())
