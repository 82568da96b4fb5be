"""Merge per-region scans into one columnar scan — the MergeScan gather.

Mirrors reference src/query/src/dist_plan/merge_scan.rs:122-259: the
frontend gathers each region's stream and concatenates. TPU-native twist:
instead of streaming ragged batches, we concatenate whole columnar scans on
the host and remap each region's tag dictionary codes into a union
dictionary with one vectorized searchsorted pass — the result feeds the same
fused device kernels as a single-region scan. (Partial-aggregate pushdown —
the Commutativity analysis — happens above this layer: when the plan is a
pure segment aggregation, per-region partials combine on the mesh instead,
greptimedb_tpu/parallel/mesh.py.)
"""

from __future__ import annotations

import collections
import threading

import numpy as np

from greptimedb_tpu.storage.region import ScanData


#: (tag, each region's dictionary version) -> (union, per-region code
#: remaps). A region's dictionary is append-only and its array is
#: rebuilt only when it grows (TagRegistry.dict_array), so (region,
#: incarnation, length) names its content: the sort of every value of
#: every region runs once per version, not in every request that gathers
_UNIONS: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()
_UNIONS_LOCK = threading.Lock()
_UNIONS_MAX = 256


def _union_dict(name: str, parts: list[ScanData]) -> tuple:
    """One tag's union dictionary over the regions' own, and each
    region's old-code -> union-code array."""
    key = None
    if all(p.region_id >= 0 for p in parts):
        key = (name, tuple((p.region_id, p.incarnation,
                            len(p.tag_dicts[name])) for p in parts))
        with _UNIONS_LOCK:
            hit = _UNIONS.get(key)
            if hit is not None:
                _UNIONS.move_to_end(key)
                return hit
    locals_ = [p.tag_dicts[name].astype(str) for p in parts]
    union = np.unique(np.concatenate(locals_))
    out = (union, [np.searchsorted(union, local).astype(np.int32)
                   for local in locals_])
    if key is not None:
        with _UNIONS_LOCK:
            _UNIONS[key] = out
            while len(_UNIONS) > _UNIONS_MAX:
                _UNIONS.popitem(last=False)
    return out


def merge_scans(parts: list[ScanData]) -> ScanData | None:
    parts = [p for p in parts if p is not None and p.num_rows > 0]
    if not parts:
        return None
    if len(parts) == 1:
        return parts[0]
    schema = parts[0].schema

    # union tag dictionaries + code remap per region
    tag_names = list(parts[0].tag_dicts.keys())
    union_dicts: dict[str, np.ndarray] = {}
    remaps: list[dict[str, np.ndarray]] = [dict() for _ in parts]
    for name in tag_names:
        union, maps = _union_dict(name, parts)
        union_dicts[name] = union
        for i, remap in enumerate(maps):
            remaps[i][name] = remap

    columns: dict[str, np.ndarray] = {}
    for cname in parts[0].columns:
        if cname in union_dicts:
            mapped = []
            for i, p in enumerate(parts):
                codes = p.columns[cname]
                remap = remaps[i][cname]
                out = np.where(codes >= 0, remap[np.clip(codes, 0, None)], -1)
                mapped.append(out.astype(np.int32))
            columns[cname] = np.concatenate(mapped)
        else:
            columns[cname] = np.concatenate([p.columns[cname] for p in parts])

    # sequences are per-region counters; partitioned tables have disjoint
    # keys across regions so cross-region LWW never arises — keep seqs as-is
    seq = np.concatenate([p.seq for p in parts])
    op_type = np.concatenate([p.op_type for p in parts])
    return ScanData(
        schema=schema,
        columns=columns,
        seq=seq,
        op_type=op_type,
        tag_dicts=union_dicts,
        num_rows=int(sum(p.num_rows for p in parts)),
        needs_dedup=any(p.needs_dedup for p in parts),
        region_id=-1,
        data_version=0,
        scan_fingerprint=tuple(
            (p.region_id, p.data_version, p.scan_fingerprint) for p in parts
        ),
    )
