"""SpGEMM traffic: seeded stand-ins for the SegFold paper's Table III
SuiteSparse matrices, at their original dimensions and density.

A traffic file reads ``{"generator": "table3", "matrices": [...]}``; each
name is a key of the configuration file, which gives the matrix's
``m``, ``n``, ``density`` and structural ``family``.  One pass of the
traffic is ``C = A @ A.T`` for every listed matrix.

The pattern of a matrix depends on its name alone (``zlib.crc32``), as a
real matrix's does, so every seed runs the same schedules; the values are
drawn from ``--seed``.  Nothing here depends on ``PYTHONHASHSEED``.
The families follow ``repro.sim.matrices`` (banded/stencil, planar mesh,
power-law graph, power network, uniform LP, Franz-like random blocks),
vectorized, without its scale-down, and with the BSR built straight from
the coordinates.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass
class BlockSparse:
    """A BSR matrix as plain arrays: blocks sorted row-major."""
    shape: Tuple[int, int]
    block: int
    brow: np.ndarray          # int32 (nb,)
    bcol: np.ndarray          # int32 (nb,)
    blocks: np.ndarray        # float32 (nb, block, block)

    def transpose(self) -> "BlockSparse":
        order = np.lexsort((self.brow, self.bcol))
        return BlockSparse((self.shape[1], self.shape[0]), self.block,
                           self.bcol[order].copy(), self.brow[order].copy(),
                           np.ascontiguousarray(
                               self.blocks[order].transpose(0, 2, 1)))


def _banded(rng, m, n, density, spread=0.02):
    nnz = max(1, int(density * m * n))
    rows = rng.integers(0, m, size=nnz)
    cols = np.clip(np.round(rows * (n / m) + rng.normal(
        0, max(spread * n, 1.5), size=nnz)), 0, n - 1)
    return rows, cols.astype(np.int64)


def _mesh(rng, m, n, density):
    side = int(np.sqrt(m))
    deg = max(2, int(density * n))
    r = np.repeat(np.arange(m), deg)
    dx = rng.integers(-2, 3, size=r.size)
    dy = rng.integers(-2, 3, size=r.size)
    c = (r % side + dx) % side + ((r // side + dy) % side) * side
    keep = c < n
    return r[keep], c[keep]


def _powerlaw(rng, m, n, density, alpha=1.8):
    target = max(1, int(density * m * n))
    pr = np.arange(1, m + 1, dtype=np.float64) ** -alpha
    pc = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    keys = np.zeros(0, np.int64)
    for _ in range(12):          # the Zipf head collides: top up in rounds
        need = target - keys.size
        if need <= 0:
            break
        rs = rng.choice(m, size=2 * need, p=pr / pr.sum())
        cs = rng.choice(n, size=2 * need, p=pc / pc.sum())
        new = rs.astype(np.int64) * n + cs
        _, first = np.unique(new, return_index=True)
        new = new[np.sort(first)]
        keys = np.concatenate([keys, new[~np.isin(new, keys)]])[:target]
    # rows and columns are relabelled so the hubs are not the first indices
    rows, cols = rng.permutation(m)[keys // n], rng.permutation(n)[keys % n]
    return rows, cols


def _powernet(rng, m, n, density):
    rows, cols = _banded(rng, m, n, density * 0.8, spread=0.01)
    hub_nnz = max(1, int(density * m * n * 0.2))
    hubs = rng.choice(m, size=max(1, m // 200), replace=False)
    return (np.concatenate([rows, rng.choice(hubs, size=hub_nnz)]),
            np.concatenate([cols, rng.integers(0, n, size=hub_nnz)]))


def _uniform(rng, m, n, density):
    target = min(max(1, int(density * m * n)), m * n)
    keys = np.unique(rng.integers(0, m * n, size=target))
    while keys.size < target:
        keys = np.unique(np.concatenate(
            [keys, rng.integers(0, m * n, size=target - keys.size)]))
    keys = rng.permutation(keys)[:target]
    return keys // n, keys % n


def _blockrand(rng, m, n, density, blocks=16):
    bm, bn = max(1, m // blocks), max(1, n // blocks)
    n_active = max(1, int(density * blocks * blocks * 6))
    cnt = max(1, int(density * m * n / n_active))
    br = rng.integers(blocks, size=n_active)
    bc = rng.integers(blocks, size=n_active)
    rows = np.repeat(br * bm, cnt) + rng.integers(0, bm, size=n_active * cnt)
    cols = np.repeat(bc * bn, cnt) + rng.integers(0, bn, size=n_active * cnt)
    return np.clip(rows, 0, m - 1), np.clip(cols, 0, n - 1)


FAMILIES = {"banded": _banded, "mesh": _mesh, "powerlaw": _powerlaw,
            "powernet": _powernet, "uniform": _uniform, "block": _blockrand}


def pattern(name: str, spec: Dict) -> Tuple[np.ndarray, np.ndarray]:
    """Unique (row, col) coordinates of matrix ``name``, row-major."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    m, n = spec["m"], spec["n"]
    rows, cols = FAMILIES[spec["family"]](rng, m, n, spec["density"])
    keys = np.unique(np.asarray(rows, np.int64) * n
                     + np.asarray(cols, np.int64))
    return keys // n, keys % n


def block_sparse(name: str, spec: Dict, block: int, seed: int) -> BlockSparse:
    """Matrix ``name`` tiled into ``block`` squares, with float32 values
    drawn from ``seed``."""
    rows, cols = pattern(name, spec)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 zlib.crc32(name.encode())])
    vals = rng.standard_normal(rows.size).astype(np.float32)
    gk = -(-spec["n"] // block)
    bkey = (rows // block) * gk + cols // block
    ukeys, which = np.unique(bkey, return_inverse=True)
    blocks = np.zeros((ukeys.size, block, block), np.float32)
    blocks[which, rows % block, cols % block] = vals
    return BlockSparse((spec["m"], spec["n"]), block,
                       (ukeys // gk).astype(np.int32),
                       (ukeys % gk).astype(np.int32), blocks)


def build(config: Dict, traffic: Dict,
          seed: int) -> List[Tuple[str, BlockSparse]]:
    """The pass's matrices in traffic order."""
    block = int(config["block"])
    return [(name, block_sparse(name, config[name], block, seed))
            for name in traffic["matrices"]]
