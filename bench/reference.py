"""The plain reference: exact k nearest neighbours and exact closest pairs.

Imports nothing of the program under test.  A device pass shortlists
candidates by the norm trick at float32 ``HIGHEST``; the host then ranks
the shortlist in float64, so the answer is exact wherever the true
answer lies inside the shortlist (the float32 error of the shortlist is
orders of magnitude below the gaps of the 64th candidate).

``precision="high"`` is the control: the same computation with every
matmul at three bf16 passes (``Precision.HIGH``, emulated by splitting
each float32 into a bf16 high and low part so that it reads the same on
every backend), ranked and reported in that precision with no float64
step.  It stands in for the program computed one step below the
precision the configuration states, and the comparison in ``check.py``
has to reject it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SHORTLIST = 64  # candidates per row ranked on the host
QUERY_BLOCK = 256  # query rows per device step of the kNN shortlist
PAIR_BLOCK = 512  # rows per device step of the closest-pair shortlist
_HIGHEST = jax.lax.Precision.HIGHEST


def _split_bf16(a):
    # reduce_precision rounds to bf16 in place and, unlike a round trip
    # through the bf16 type, is never elided by the compiler
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def cross(a, b, precision: str):
    """a (r, d) · b (n, d)ᵀ at ``"highest"`` or at ``"high"``."""
    if precision == "highest":
        return jnp.dot(a, b.T, precision=_HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    (ah, al), (bh, bl) = _split_bf16(a), _split_bf16(b)
    # bf16 × bf16 products are exact in float32: three passes, the
    # low × low term dropped, as Precision.HIGH does on the MXU
    return (jnp.dot(ah, bh.T, precision=_HIGHEST)
            + jnp.dot(ah, bl.T, precision=_HIGHEST)
            + jnp.dot(al, bh.T, precision=_HIGHEST))


def _blocks(rows: int, block: int) -> int:
    return -(-rows // block)


@partial(jax.jit, static_argnames=("width", "precision", "block"))
def _knn_shortlist(x, xn, q, *, width: int, precision: str, block: int):
    qb = q.reshape(-1, block, q.shape[1])

    def one(qr):
        d2 = (jnp.sum(qr * qr, 1)[:, None] + xn[None, :]
              - 2.0 * cross(qr, x, precision))
        neg, ids = jax.lax.top_k(-d2, width)
        return -neg, ids

    d2, ids = jax.lax.map(one, qb)
    return d2.reshape(-1, width), ids.reshape(-1, width)


def _pad_rows(a: np.ndarray, block: int) -> np.ndarray:
    total = _blocks(len(a), block) * block
    return np.concatenate([a, np.repeat(a[-1:], total - len(a), axis=0)])


def knn(x_dev, x_host: np.ndarray, queries: np.ndarray, k: int,
        precision: str = "highest"):
    """(ids (Q, k) int64, distances (Q, k) float64) of each query's k
    nearest rows, ascending, ties by lower id."""
    q = np.asarray(queries, np.float32)
    xn = jnp.sum(x_dev * x_dev, axis=1)
    width = max(k, SHORTLIST) if precision == "highest" else k
    width = min(width, x_host.shape[0])
    block = min(QUERY_BLOCK, len(q))
    d2, cand = _knn_shortlist(x_dev, xn, jnp.asarray(_pad_rows(q, block)),
                              width=width, precision=precision, block=block)
    d2 = np.asarray(d2)[:len(q)]
    cand = np.asarray(cand)[:len(q)].astype(np.int64)
    if precision != "highest":
        return cand[:, :k], np.sqrt(np.maximum(d2[:, :k], 0.0)).astype(
            np.float64)
    ids = np.empty((len(q), k), np.int64)
    dist = np.empty((len(q), k), np.float64)
    for s in range(0, len(q), QUERY_BLOCK):
        c = cand[s:s + QUERY_BLOCK]
        diff = x_host[c].astype(np.float64) - q[s:s + QUERY_BLOCK, None, :]
        dd = np.sqrt(np.einsum("qcd,qcd->qc", diff, diff))
        order = np.lexsort((c, dd), axis=1)[:, :k]
        ids[s:s + QUERY_BLOCK] = np.take_along_axis(c, order, axis=1)
        dist[s:s + QUERY_BLOCK] = np.take_along_axis(dd, order, axis=1)
    return ids, dist


def _pair_d2(x, xn, rows, precision):
    n = x.shape[0]
    d2 = xn[rows][:, None] + xn[None, :] - 2.0 * cross(x[rows], x, precision)
    return jnp.where(jnp.arange(n)[None, :] > rows[:, None], d2, jnp.inf)


@partial(jax.jit, static_argnames=("precision", "block"))
def _pair_rowmin(x, xn, *, precision: str, block: int):
    """Each row's smallest squared distance to a later row."""
    n = x.shape[0]
    starts = jnp.arange(_blocks(n, block)) * block

    def one(start):
        rows = jnp.minimum(start + jnp.arange(block), n - 1)
        return jnp.min(_pair_d2(x, xn, rows, precision), axis=1)

    return jax.lax.map(one, starts).reshape(-1)[:n]


@partial(jax.jit, static_argnames=("width", "precision"))
def _pair_shortlist(x, xn, rows, *, width: int, precision: str):
    """(block, width) nearest later rows of each of ``rows``."""
    neg, ids = jax.lax.top_k(-_pair_d2(x, xn, rows, precision), width)
    return -neg, ids


def closest_pairs(x_dev, x_host: np.ndarray, k: int,
                  precision: str = "highest"):
    """(pairs (k, 2) int64 with i < j, distances (k,) float64) of the k
    closest pairs of rows, ascending.

    A first device pass finds each row's nearest later row.  The k
    smallest of those minima are k distinct pairs, so the k-th of them
    bounds the k-th closest distance; every row whose minimum lies
    within that bound plus the float32 norm-trick error is shortlisted
    again, and its candidates ranked in float64.
    """
    n = x_host.shape[0]
    kk = min(k, n * (n - 1) // 2)
    xn = jnp.sum(x_dev * x_dev, axis=1)
    block = min(PAIR_BLOCK, n)
    rowmin = np.asarray(_pair_rowmin(x_dev, xn, precision=precision,
                                     block=block))
    kth = float(np.partition(rowmin, kk - 1)[kk - 1])
    exact = precision == "highest"
    # float32 norm-trick error; the control ranks in its own precision
    slack = 1e-5 * 2.0 * float(np.max(np.asarray(xn))) if exact else 0.0
    thr2 = kth + 2.0 * slack
    rows = np.flatnonzero(rowmin <= thr2)
    width = min(SHORTLIST if exact else kk, n - 1)
    ii, jj, dd = [], [], []
    for s in range(0, len(rows), block):
        part = rows[s:s + block]
        padded = np.resize(part, block).astype(np.int32)  # pad by repeats
        cd, cc = _pair_shortlist(x_dev, xn, jnp.asarray(padded),
                                 width=width, precision=precision)
        cd, cc = np.asarray(cd)[:len(part)], np.asarray(cc)[:len(part)]
        r, c = np.nonzero(cd <= thr2)
        ii.append(part[r].astype(np.int64))
        jj.append(cc[r, c].astype(np.int64))
        dd.append(cd[r, c].astype(np.float64))
    ii, jj, dd = np.concatenate(ii), np.concatenate(jj), np.concatenate(dd)
    if exact:
        diff = x_host[ii].astype(np.float64) - x_host[jj].astype(np.float64)
        dd = np.sqrt(np.einsum("pd,pd->p", diff, diff))
    else:
        dd = np.sqrt(np.maximum(dd, 0.0))
    order = np.lexsort((jj, ii, dd))[:kk]
    return np.stack([ii[order], jj[order]], axis=1), dd[order]
