"""Plain references the benchmark's ``correct`` is decided against.

Host numpy (and scipy's LOBPCG for the one large eigenspace), float64,
importing nothing of the program and taking nothing it made but the
answers under test.  The pieces:

* the unnormalized Laplacian L = D - A as a matvec and as a dense
  matrix;
* :func:`block_residual`, the plain-Laplacian block residual
  ||L Q - Q (Q^T L Q)||_F / ||L Q||_F of a panel's orthonormal basis Q
  (the dilation the program plans never enters it);
* :func:`bottom_eigenspace`, the lowest eigenvectors of a large sparse
  L by LOBPCG, and :func:`subspace_sin`, the sine of the largest
  principal angle between that space and a panel's span;
* dense spectral clustering (:func:`reference_spectrum`,
  :func:`reference_labels`): ``eigh``, eigenvectors 1..clusters
  row-normalized, plain k-means, and :func:`agreement` up to a
  permutation of cluster ids.
"""
from __future__ import annotations

import itertools

import numpy as np


def laplacian_matvec(src, dst, w, n: int, v: np.ndarray) -> np.ndarray:
    """L v for the undirected graph with edges (src, dst, w), float64.
    ``v`` is (n,) or (n, k)."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float64)
    v = np.asarray(v, np.float64)
    vec = v.ndim == 1
    v2 = v[:, None] if vec else v
    deg = (np.bincount(src, weights=w, minlength=n)
           + np.bincount(dst, weights=w, minlength=n))
    out = deg[:, None] * v2
    for j in range(v2.shape[1]):
        out[:, j] -= np.bincount(dst, weights=w * v2[src, j], minlength=n)
        out[:, j] -= np.bincount(src, weights=w * v2[dst, j], minlength=n)
    return out[:, 0] if vec else out


def orthonormal_basis(v: np.ndarray) -> np.ndarray:
    q, _ = np.linalg.qr(np.asarray(v, np.float64))
    return q


def block_residual(src, dst, w, n: int, panel) -> float:
    """||L Q - Q (Q^T L Q)||_F / ||L Q||_F for Q an orthonormal basis of
    the panel's span (a panel need not be orthonormal to be served)."""
    q = orthonormal_basis(panel)
    lq = laplacian_matvec(src, dst, w, n, q)
    r = lq - q @ (q.T @ lq)
    return float(np.linalg.norm(r) / max(np.linalg.norm(lq), 1e-300))


def subspace_sin(u: np.ndarray, panel) -> float:
    """Sine of the largest principal angle between span(u) (orthonormal
    columns) and span(panel): how much of u the panel misses."""
    q = orthonormal_basis(panel)
    miss = u - q @ (q.T @ u)
    return float(np.linalg.norm(miss, 2))


def bottom_eigenspace(src, dst, w, n: int, start: np.ndarray,
                      tol: float = 1e-9, maxiter: int = 400):
    """Lowest ``start.shape[1]`` eigenpairs of L by LOBPCG in float64,
    from ``start`` (e.g. planted community indicators, which lie close
    to the community eigenspace).  Returns (eigenvalues, (n, m)
    orthonormal eigenvectors, max relative eigen-residual)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.linalg import lobpcg

    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w = np.asarray(w, np.float64)
    a = coo_matrix((np.concatenate([w, w]),
                    (np.concatenate([src, dst]), np.concatenate([dst, src]))),
                   shape=(n, n)).tocsr()
    deg = np.asarray(a.sum(axis=1)).ravel()
    from scipy.sparse import diags

    lap = (diags(deg) - a).tocsr()
    x0 = orthonormal_basis(start)
    vals, vecs = lobpcg(lap, x0, largest=False, tol=tol, maxiter=maxiter)
    order = np.argsort(vals)
    vals, vecs = vals[order], orthonormal_basis(vecs[:, order])
    res = lap @ vecs - vecs * vals[None, :]
    scale = max(float(np.abs(vals).max()), 1.0)
    rel = float(np.linalg.norm(res, axis=0).max() / scale)
    return vals, vecs, rel


def dense_laplacian(src, dst, w, n: int) -> np.ndarray:
    lap = np.zeros((n, n), np.float64)
    np.add.at(lap, (np.asarray(src), np.asarray(dst)), -np.asarray(w, np.float64))
    np.add.at(lap, (np.asarray(dst), np.asarray(src)), -np.asarray(w, np.float64))
    lap[np.diag_indices(n)] = -lap.sum(1)
    return lap


def agreement(labels, truth, k: int) -> float:
    """Share of nodes whose label matches ``truth`` under the best
    permutation of the k cluster ids."""
    conf = np.zeros((k, k))
    np.add.at(conf, (np.asarray(labels), np.asarray(truth)), 1)
    best = max(conf[list(p), range(k)].sum()
               for p in itertools.permutations(range(k)))
    return float(best / len(truth))


def plain_kmeans(x, k: int, seed: int, restarts: int = 8,
                 iters: int = 100):
    """Lloyd's k-means with k-means++ seeding, best of ``restarts``."""
    rng = np.random.default_rng(seed)
    best = None
    for _ in range(restarts):
        c = x[rng.integers(len(x))][None]
        for _ in range(k - 1):
            d2 = ((x[:, None] - c[None]) ** 2).sum(-1).min(1)
            c = np.vstack([c, x[rng.choice(len(x), p=d2 / d2.sum())]])
        for _ in range(iters):
            lab = ((x[:, None] - c[None]) ** 2).sum(-1).argmin(1)
            new = np.stack([x[lab == j].mean(0) if (lab == j).any()
                            else c[j] for j in range(k)])
            if np.allclose(new, c):
                break
            c = new
        lab = ((x[:, None] - c[None]) ** 2).sum(-1).argmin(1)
        inertia = float(((x - c[lab]) ** 2).sum())
        if best is None or inertia < best[0]:
            best = (inertia, lab)
    return best[1]


def reference_spectrum(src, dst, w, n: int):
    """(eigenvalues, eigenvectors) of the dense L, ascending."""
    return np.linalg.eigh(dense_laplacian(src, dst, w, n))


def reference_labels(vecs: np.ndarray, clusters: int, seed: int):
    """Plain spectral clustering from L's eigenvectors: vectors
    1..clusters (the trivial one dropped, as the service serves),
    row-normalized, then plain k-means."""
    emb = vecs[:, 1:1 + clusters]
    emb = emb / np.maximum(np.linalg.norm(emb, axis=1, keepdims=True),
                           1e-12)
    return plain_kmeans(emb, clusters, seed)
