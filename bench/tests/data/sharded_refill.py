"""A loop of its own: ``refill`` through the sharded path.

``PlanService.assemble(..., method="sharded")`` on one hot structure
over a mesh of every device JAX sees: the plan routes each triplet to
the chip that owns its row block, and every request refills the
block-row ``ShardedCSC`` with new values.  The check compares one CSC,
so :meth:`finish` rebuilds it from the blocks' host copies once the
window has closed.

Copied into a checkout as ``bench/traffic/sharded_refill.py``, a
traffic mix ``{"loop": "sharded_refill", ...}`` runs it.
"""
from __future__ import annotations

import numpy as np

from bench import loops


def csc_from_blocks(blocks: dict, M: int) -> dict:
    """The global CSC (host arrays ``nnz``, ``indptr``, ``indices``,
    ``data``) of a block-row ``ShardedCSC``'s host copies: block ``b``
    holds rows ``[b * rpb, (b + 1) * rpb)`` numbered from 0 within the
    block, in its first ``nnz[b]`` slots, in column order."""
    p, n1 = blocks["indptr"].shape
    rpb = -(-M // p)
    cols, rows, data = [], [], []
    for b in range(p):
        nz = int(blocks["nnz"][b])
        counts = np.diff(blocks["indptr"][b])
        cols.append(np.repeat(np.arange(n1 - 1), counts)[:nz])
        rows.append(blocks["indices"][b, :nz].astype(np.int64) + b * rpb)
        data.append(blocks["data"][b, :nz])
    cols = np.concatenate(cols)
    order = np.argsort(cols, kind="stable")   # rows ascend within a column
    counts = np.bincount(cols, minlength=n1 - 1)
    return {"nnz": int(cols.size),
            "indptr": np.concatenate([[0], np.cumsum(counts)]),
            "indices": np.concatenate(rows)[order],
            "data": np.concatenate(data)[order]}


class ShardedRefillLoop(loops.RefillLoop):
    def _assemble(self, vals):
        S = self.svc.assemble(self.ii, self.jj, vals, self.shape,
                              method="sharded")
        S.data.block_until_ready()
        return S

    def finish(self) -> None:
        super().finish()
        M = self.shape[0]
        self.sample.items = [(k, csc_from_blocks(got, M))
                             for k, got in self.sample.items]


LOOP = ShardedRefillLoop
