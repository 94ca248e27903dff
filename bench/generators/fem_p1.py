"""3-D P1 (linear tetrahedral) stiffness assembly triplets.

The FEM assembly benchmark of Cuvelier, Japhet & Scarella
(arXiv:1401.3301, the 3-D P1 case): every tetrahedron contributes its
4 x 4 element stiffness matrix, 16 triplets, and ``sparse`` sums them
into the global matrix.  The mesh is the unit cube cut into ``n**3``
cubes, each split into the 6 Kuhn tetrahedra (all sharing the cube's
main diagonal), so ``L = 96 n**3`` and ``M = N = (n + 1)**3``.  Vertices
are labelled in the natural (lexicographic, x fastest) order in which a
structured mesh generator emits them; the structure is the same for
every seed.

Element values are ``coef[e] * K_ref[type(e)]``: the exact P1 stiffness
matrix of each of the 6 Kuhn tetrahedron types at mesh size ``h``,
scaled by a diffusion coefficient per element drawn from the seed (one
Newton or time step of a problem with a varying coefficient).
"""
from __future__ import annotations

import itertools

import numpy as np

#: the 6 Kuhn tetrahedra of the unit cube: the path (0,0,0) -> (1,1,1)
#: adding the unit vectors in the order of each axis permutation
_AXIS_ORDERS = tuple(itertools.permutations(range(3)))


def _kuhn_offsets() -> np.ndarray:
    """``[6, 4, 3]`` integer corner offsets of the Kuhn tetrahedra."""
    out = np.zeros((6, 4, 3), np.int64)
    for t, order in enumerate(_AXIS_ORDERS):
        for k, axis in enumerate(order):
            out[t, k + 1:, axis] += 1
    return out


def reference_stiffness(h: float) -> np.ndarray:
    """``[6, 4, 4]`` P1 stiffness matrices of the Kuhn tetrahedra of a
    cube of side ``h``: ``vol * G G^T`` with ``G`` the gradients of the
    barycentric coordinates."""
    out = np.zeros((6, 4, 4))
    for t, corners in enumerate(_kuhn_offsets().astype(np.float64) * h):
        A = np.hstack([np.ones((4, 1)), corners])
        grads = np.linalg.inv(A)[1:].T            # [4 vertices, 3]
        vol = abs(np.linalg.det(A)) / 6.0
        out[t] = vol * grads @ grads.T
    return out


def mesh(n: int):
    """Unit-offset triplet indices ``(ii, jj)`` of the P1 stiffness
    stream, as int32, and the ``[6 n**3]`` Kuhn type of each element.

    Element order: cube by cube (x fastest), 6 tetrahedra per cube;
    within an element the 16 triplets run row-major over its 4 x 4
    local matrix.  Vertex ``(x, y, z)`` has the label
    ``x + (n+1) y + (n+1)**2 z``.
    """
    nv = n + 1
    cx, cy, cz = np.meshgrid(np.arange(n), np.arange(n), np.arange(n),
                             indexing="ij")
    base = (cx + nv * cy + nv * nv * cz).transpose(2, 1, 0).ravel()
    off = _kuhn_offsets()
    step = off[..., 0] + nv * off[..., 1] + nv * nv * off[..., 2]  # [6, 4]
    verts = base[:, None, None] + step[None]                      # [c, 6, 4]
    verts = verts.reshape(-1, 4)                                  # [e, 4]
    ii = np.repeat(verts, 4, axis=1).ravel() + 1
    jj = np.tile(verts, (1, 4)).ravel() + 1
    types = np.tile(np.arange(6, dtype=np.int8), n ** 3)
    return ii.astype(np.int32), jj.astype(np.int32), types


def values(types: np.ndarray, n: int, seed: int, k: int) -> np.ndarray:
    """Float64 triplet values of value set ``k``: a seeded coefficient
    in [0.5, 2) per element times its reference stiffness matrix."""
    rng = np.random.default_rng([int(seed), 0xFE, int(k)])
    coef = rng.uniform(0.5, 2.0, size=types.shape[0])
    kref = reference_stiffness(1.0 / n).reshape(6, 16)
    return (coef[:, None] * kref[types]).ravel()


def generate(cfg: dict, seed: int, k: int = 0):
    """The configuration's structure (the same for every ``seed`` and
    ``k``): ``(ii, jj, (M, N), state)``; ``state`` is what
    :func:`value_set` needs."""
    n = int(cfg["n"])
    ii, jj, types = mesh(n)
    m = (n + 1) ** 3
    return ii, jj, (m, m), {"types": types, "n": n}


def value_set(state: dict, seed: int, k: int) -> np.ndarray:
    """Value set ``k`` of the structure ``generate`` made."""
    return values(state["types"], state["n"], seed, k)
