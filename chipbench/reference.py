"""The plain reference the benchmark judges the served store against.

It imports nothing of the program.  It knows the base graph the
benchmark generated, and the raw insert and delete batches the clients
sent, and from those alone it says:

- which edges each generation holds (:class:`EdgeState`): the base graph
  with the acked batches applied in order, ``E' = (E minus D) union I``;
- what a k-step reverse walk at a generation returns (:func:`walk`):
  ``x_{k+1}[u] = sum over edges (u, v) of x_k[v]`` from seed weights
  ``x_0``, weighted path counts in float64, many walks at once;
- how many bytes each walk step needs at the least (:func:`walk`'s
  ``bytes``): 8 B for every edge (u, v) whose ``x_k[v]`` is nonzero (its
  id and the value read) and 8 B for every nonzero of ``x_{k+1}``,
  counted from the frontier the walk really has;
- what the write-ahead log holds (:func:`read_wal`), decoded from its
  documented record format.

The base graph is symmetric (every generator symmetrises), so the base
CSR is its own transpose; the delta of a generation is kept as explicit
signed edges and may be asymmetric.
"""
from __future__ import annotations

import os
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: dense steps split the base matrix into this many row blocks
THREADS = 8
#: a step stays sparse while the edges it reads are below this share of |E|
SPARSE_SHARE = 1.0 / 16


def keys_of(src, dst) -> np.ndarray:
    return (np.asarray(src, np.int64) << 32) | np.asarray(dst, np.int64)


class EdgeState:
    """The edge set and weights of the served graph, generation by
    generation: the base CSR and the changes of every applied batch."""

    def __init__(self, offsets: np.ndarray, dst: np.ndarray,
                 wgt: np.ndarray, n: int):
        self.offsets = np.asarray(offsets, np.int64)
        self.dst = np.asarray(dst, np.int32)
        self.wgt = np.asarray(wgt, np.float32)
        self.n = int(n)
        self.deg = np.diff(self.offsets)
        self._base_keys = None
        #: key -> weight of an edge present now (None: absent now)
        self.changed: dict = {}

    @property
    def base_keys(self) -> np.ndarray:
        if self._base_keys is None:
            src = np.repeat(np.arange(self.n, dtype=np.int64), self.deg)
            self._base_keys = keys_of(src, self.dst)
        return self._base_keys

    def in_base(self, keys: np.ndarray) -> np.ndarray:
        bk = self.base_keys
        at = np.minimum(np.searchsorted(bk, keys), bk.shape[0] - 1)
        return bk[at] == keys

    def apply(self, ins_src, ins_dst, ins_wgt, del_src, del_dst) -> None:
        """One batch: deletes first, then inserts (an insert wins over a
        delete of the same key; a repeated insert keeps its first weight)."""
        for k in keys_of(del_src, del_dst).tolist():
            self.changed[k] = None
        ik = keys_of(ins_src, ins_dst)
        seen = set()
        for k, w in zip(ik.tolist(), np.asarray(ins_wgt, np.float32).tolist()):
            if k not in seen:
                seen.add(k)
                self.changed[k] = w

    def delta(self):
        """Signed edges ``(u, v, s)`` that turn the base into the current
        edge set: ``s = +1`` added, ``-1`` removed."""
        if not self.changed:
            z = np.zeros(0, np.int64)
            return z, z, z
        keys = np.fromiter(self.changed.keys(), np.int64, len(self.changed))
        present = np.array([w is not None for w in self.changed.values()])
        base = self.in_base(keys)
        sign = present.astype(np.int64) - base.astype(np.int64)
        keep = sign != 0
        keys, sign = keys[keep], sign[keep]
        return keys >> 32, keys & 0xFFFFFFFF, sign

    def edges(self):
        """The current (keys, weights), sorted by key."""
        bk, bw = self.base_keys, self.wgt
        if not self.changed:
            return bk, bw
        keys = np.fromiter(self.changed.keys(), np.int64, len(self.changed))
        at = np.minimum(np.searchsorted(bk, keys), bk.shape[0] - 1)
        gone = at[bk[at] == keys]
        bk, bw = np.delete(bk, gone), np.delete(bw, gone)
        live = sorted((k, w) for k, w in self.changed.items() if w is not None)
        ak = np.array([k for k, _ in live], np.int64)
        aw = np.array([w for _, w in live], np.float32)
        at = np.searchsorted(bk, ak)
        return np.insert(bk, at, ak), np.insert(bw, at, aw)


def _dense_step(csr_blocks, x: np.ndarray) -> np.ndarray:
    with ThreadPoolExecutor(len(csr_blocks)) as ex:
        parts = list(ex.map(lambda a: a @ x, csr_blocks))
    return np.concatenate(parts, axis=0)


class Walker:
    """Walks many requests over one base graph and per-generation deltas."""

    def __init__(self, state: EdgeState):
        import scipy.sparse as sp

        self.s = state
        n, off = state.n, state.offsets
        ones = np.ones(state.dst.shape[0], np.float32)
        a = sp.csr_matrix((ones, state.dst, off), shape=(n, n))
        cuts = np.searchsorted(
            off, np.linspace(0, off[-1], THREADS + 1).astype(np.int64)
        )
        cuts[0], cuts[-1] = 0, n
        self.blocks = [a[cuts[i]:cuts[i + 1]] for i in range(THREADS)]

    def _candidates(self, sup, du, dv):
        """Rows that a frontier ``sup`` can reach in one step."""
        s = self.s
        lo = s.offsets[sup]
        cnt = s.offsets[sup + 1] - lo
        idx = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())
        return np.union1d(s.dst[idx], du[np.isin(dv, sup)])

    def _sparse_step(self, rows, sup, vals, du, dv, ds):
        """Sums of the candidate ``rows`` from a sparse frontier
        ``(sup sorted, vals [len(sup), k])``."""
        s = self.s
        rlo = s.offsets[rows]
        rc = s.offsets[rows + 1] - rlo
        eidx = np.repeat(rlo - np.cumsum(rc) + rc, rc) + np.arange(rc.sum())
        out = np.zeros((rows.shape[0], vals.shape[1]))
        if eidx.shape[0]:
            v = s.dst[eidx].astype(np.int64)
            at = np.minimum(np.searchsorted(sup, v), sup.shape[0] - 1)
            contrib = np.where((sup[at] == v)[:, None], vals[at], 0.0)
            full = rc > 0
            out[full] = np.add.reduceat(
                contrib, (np.cumsum(rc) - rc)[full], axis=0
            )
        if du.shape[0]:
            at = np.minimum(np.searchsorted(sup, dv), sup.shape[0] - 1)
            hit = sup[at] == dv
            np.add.at(out, np.searchsorted(rows, du[hit]),
                      ds[hit, None] * vals[at[hit]])
        return out

    def walk(self, delta, seeds_list, steps: int, *, weights_list=None,
             round_to=None):
        """Walks from ``seeds_list`` (one array of seed vertices per walk,
        each occurrence a visit of its weight in ``weights_list``, or 1)
        at one generation, ``delta`` its signed edges.  Returns (:class:`Visits` of the k walks, ``bytes``
        [k] the least bytes each walk's steps read and write).

        ``round_to`` (a numpy dtype) stores each step's result in that
        type: the lower-precision control.
        """
        s = self.s
        m = s.dst.shape[0]
        du, dv, ds = (np.asarray(a, np.int64) for a in delta)
        ds_f = ds.astype(np.float64)
        indeg = s.deg.astype(np.float64)
        np.add.at(indeg, dv, ds_f)
        k = len(seeds_list)
        nbytes = np.zeros(k)
        cols = [np.asarray(x, np.int64) for x in seeds_list]
        sup = np.unique(np.concatenate(cols))
        vals = np.zeros((sup.shape[0], k))
        for j, x in enumerate(cols):
            w = 1.0 if weights_list is None else np.asarray(
                weights_list[j], np.float32).astype(np.float64)
            np.add.at(vals[:, j], np.searchsorted(sup, x), w)
        dense = None
        for _ in range(steps):
            if dense is None:
                nbytes += (vals != 0).astype(np.float64).T @ (8 * indeg[sup])
                if s.deg[sup].sum() < SPARSE_SHARE * m:
                    rows = self._candidates(sup, du, dv)
                    if s.deg[rows].sum() < SPARSE_SHARE * m:
                        vals = self._sparse_step(rows, sup, vals, du, dv, ds_f)
                        if round_to is not None:
                            vals = vals.astype(round_to).astype(np.float64)
                        keep = np.any(vals != 0, axis=1)
                        sup, vals = rows[keep], vals[keep]
                        nbytes += 8 * np.count_nonzero(vals, axis=0)
                        continue
                dense = np.zeros((s.n, k))
                dense[sup] = vals
            else:
                nbytes += (dense != 0).astype(np.float64).T @ (8 * indeg)
            nxt = _dense_step(self.blocks, dense)
            if du.shape[0]:
                np.add.at(nxt, du, ds_f[:, None] * dense[dv])
            if round_to is not None:
                nxt = nxt.astype(round_to).astype(np.float64)
            dense = nxt
            nbytes += 8 * np.count_nonzero(dense, axis=0)
        if dense is None:
            return Visits(s.n, sup=sup, vals=vals), nbytes.astype(np.int64)
        return Visits(s.n, dense=dense), nbytes.astype(np.int64)


class Visits:
    """The k walks' visit vectors, kept sparse while the walk stayed so."""

    def __init__(self, n: int, *, sup=None, vals=None, dense=None):
        self.n, self.sup, self.vals, self.dense = n, sup, vals, dense

    def column(self, j: int) -> np.ndarray:
        if self.dense is not None:
            return self.dense[:, j]
        out = np.zeros(self.n)
        out[self.sup] = self.vals[:, j]
        return out


#: the error of an answer of the wrong shape or with a non-finite value
UNREADABLE = 1e30


def walk_error(program: np.ndarray, ref: np.ndarray) -> float:
    """The gap of one walk's answer, ``max |p - r| / max |r|`` over its
    vertices: relative to the walk's largest count, because a walk that
    sums by prefix differences in float32 rounds each entry to the
    magnitude of the prefix it is read from, not to its own."""
    p = np.asarray(program, np.float64)
    if p.shape != ref.shape or not np.all(np.isfinite(p)):
        return UNREADABLE
    scale = float(np.max(np.abs(ref)))
    if scale == 0.0:
        return float(np.max(np.abs(p)))
    return float(np.max(np.abs(p - ref))) / scale


# -- the write-ahead log, decoded from its record format -------------------
#: magic "WAL1", sequence, vertex bound, op count, CRC32 of the payload
_HEADER = struct.Struct("<IQIII")
_MAGIC = 0x314C4157


def read_wal(wal_dir: str):
    """Every complete record of the log, in order: a list of
    ``(seq, src, dst, wgt, is_delete)``, and the number of records whose
    CRC or framing is bad."""
    names = sorted(
        f for f in os.listdir(wal_dir)
        if f.startswith("wal-") and f.endswith(".seg")
    )
    records, bad = [], 0
    for name in names:
        with open(os.path.join(wal_dir, name), "rb") as f:
            data = f.read()
        pos = 0
        while pos + _HEADER.size <= len(data):
            magic, seq, _nv, n, crc = _HEADER.unpack_from(data, pos)
            size = n * 12 + (n + 7) // 8
            body = data[pos + _HEADER.size: pos + _HEADER.size + size]
            if magic != _MAGIC or len(body) < size:
                bad += 1
                break
            if zlib.crc32(body) != crc:
                bad += 1
            src = np.frombuffer(body, np.int32, n, 0)
            dst = np.frombuffer(body, np.int32, n, 4 * n)
            wgt = np.frombuffer(body, np.float32, n, 8 * n)
            dele = np.unpackbits(
                np.frombuffer(body, np.uint8, offset=12 * n), count=n
            ).astype(bool)
            records.append((seq, src, dst, wgt, dele))
            pos += _HEADER.size + size
    return records, bad


def canonical_ops(ins_src, ins_dst, ins_wgt, del_src, del_dst):
    """The op stream a batch should be logged as: one op per key, sorted
    by key, an insert winning over a delete of the same key and the first
    of repeated inserts kept."""
    ik = keys_of(ins_src, ins_dst)
    ik, first = np.unique(ik, return_index=True)
    iw = np.asarray(ins_wgt, np.float32)[first]
    dk = np.unique(keys_of(del_src, del_dst))
    dk = dk[~np.isin(dk, ik)]
    keys = np.concatenate([ik, dk])
    wgt = np.concatenate([iw, np.zeros(dk.shape[0], np.float32)])
    dele = np.concatenate([np.zeros(ik.shape[0], bool), np.ones(dk.shape[0], bool)])
    order = np.argsort(keys, kind="stable")
    return keys[order], wgt[order], dele[order]
