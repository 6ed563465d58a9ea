"""Reed-Solomon Vandermonde (jerasure's ``technique=reed_sol_van``)
over GF(2^8): the systematic generator ``V[k:] @ inv(V[:k])`` of the
Vandermonde matrix ``V[i, j] = i**j`` (i = 0 .. k+m-1), its columns
scaled so that the first parity row is all ones."""

from __future__ import annotations

import numpy as np

from benchmark.reference import gf_inv, gf_mul, gf_pow, invert, matmul

#: the ``<plugin>:<technique>`` profiles this file is the reference for
NAMES = ("jax_tpu:reed_sol_van", "jerasure:reed_sol_van")


def rs_vandermonde(k: int, m: int) -> np.ndarray:
    """[m, k] parity rows of the systematic Vandermonde RS code."""
    v = np.array([[gf_pow(i, j) for j in range(k)] for i in range(k + m)],
                 dtype=np.uint8)
    c = matmul(v[k:], invert(v[:k]))
    for j in range(k):
        f = gf_inv(int(c[0, j]))
        for i in range(m):
            c[i, j] = gf_mul(int(c[i, j]), f)
    return c


def layout(profile: dict):
    """(chunk count, data positions, [(chunk map, generator)])."""
    k, m = int(profile["k"]), int(profile["m"])
    return k + m, list(range(k)), [("D" * k + "c" * m, rs_vandermonde(k, m))]
