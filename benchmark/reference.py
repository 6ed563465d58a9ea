"""The plain reference the benchmark's checks compare with.

It imports nothing of the system under test and takes nothing it made:
GF(2^8) arithmetic from its tables (primitive polynomial 0x11d), a
layered code built from the generator matrices of the code a
configuration states, and the striping of an object over its data
shards, all written out here in numpy. Each code's generators come from
its own file under ``benchmark/codes/`` (see `code_module`).

An object of ``S`` bytes is cut into stripes of ``k * stripe_unit``
bytes; data chunk ``i`` of each stripe goes to the shard at the i-th
data position, and a shard is the concatenation of its chunks.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables():
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:] = exp[:255]
    return exp, log


EXP, LOG = _tables()
_A, _B = np.meshgrid(np.arange(256), np.arange(256), indexing="ij")
#: MUL[a, b] = a * b in GF(2^8)
MUL = np.where((_A == 0) | (_B == 0), 0,
               EXP[(LOG[_A] + LOG[_B]) % 255]).astype(np.uint8)
del _A, _B


def gf_mul(a: int, b: int) -> int:
    return int(MUL[a, b])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def gf_pow(a: int, n: int) -> int:
    r = 1
    for _ in range(n):
        r = gf_mul(r, a)
    return r


def matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in range(b.shape[1]):
            acc = 0
            for t in range(a.shape[1]):
                acc ^= gf_mul(int(a[i, t]), int(b[t, j]))
            out[i, j] = acc
    return out


def invert(mat: np.ndarray) -> np.ndarray:
    """Gauss-Jordan inverse of a square matrix over GF(2^8)."""
    n = mat.shape[0]
    a = [[int(x) for x in row] for row in mat]
    inv = [[int(i == j) for j in range(n)] for i in range(n)]
    for col in range(n):
        piv = next((r for r in range(col, n) if a[r][col]), None)
        if piv is None:
            raise ValueError("singular matrix")
        a[col], a[piv] = a[piv], a[col]
        inv[col], inv[piv] = inv[piv], inv[col]
        f = gf_inv(a[col][col])
        a[col] = [gf_mul(x, f) for x in a[col]]
        inv[col] = [gf_mul(x, f) for x in inv[col]]
        for r in range(n):
            if r != col and a[r][col]:
                g = a[r][col]
                a[r] = [x ^ gf_mul(g, y) for x, y in zip(a[r], a[col])]
                inv[r] = [x ^ gf_mul(g, y) for x, y in zip(inv[r], inv[col])]
    return np.array(inv, dtype=np.uint8)


def encode_rows(gen: np.ndarray, data: np.ndarray) -> np.ndarray:
    """gen [m, k] applied to data rows [k, L] -> parity rows [m, L]."""
    out = np.zeros((gen.shape[0], data.shape[1]), dtype=np.uint8)
    for i in range(gen.shape[0]):
        for j in range(gen.shape[1]):
            g = int(gen[i, j])
            if g == 1:
                out[i] ^= data[j]
            elif g:
                out[i] ^= MUL[g][data[j]]
    return out


def code_module(profile: dict):
    """The file of ``benchmark/codes/`` whose ``NAMES`` list the
    profile as ``<plugin>:<technique>``, or as ``<plugin>`` for a
    plugin that takes no technique."""
    import importlib
    import pkgutil

    from benchmark import codes
    name = profile["plugin"]
    if profile.get("technique"):
        name += ":" + profile["technique"]
    for info in pkgutil.iter_modules(codes.__path__):
        mod = importlib.import_module("benchmark.codes." + info.name)
        if name in getattr(mod, "NAMES", ()):
            return mod
    raise ValueError("no reference code for profile %r" % (profile,))


class Code:
    """The erasure code a configuration's profile states: ``n`` chunks,
    the data and parity positions, and layers, each a chunk map
    (``D`` input, ``c`` output, ``_`` not in the layer) and the
    generator rows that make its outputs from its inputs."""

    def __init__(self, profile: dict):
        self.k = int(profile["k"])
        self.n, self.data_positions, self.layers = \
            code_module(profile).layout(profile)
        self.parity_positions = [i for i in range(self.n)
                                 if i not in self.data_positions]

    def encode(self, data: np.ndarray) -> np.ndarray:
        """Logical data rows [k, L] -> every chunk [n, L] by position."""
        full = np.zeros((self.n, data.shape[1]), dtype=np.uint8)
        full[self.data_positions] = data
        for chunk_map, gen in self.layers:
            ins = [i for i, c in enumerate(chunk_map) if c == "D"]
            outs = [i for i, c in enumerate(chunk_map) if c == "c"]
            full[outs] = encode_rows(gen, full[ins])
        return full

    def parity(self, data: np.ndarray) -> np.ndarray:
        """Logical data rows [k, L] -> parity rows in position order."""
        return self.encode(data)[self.parity_positions]


def stripe(obj: bytes, k: int, stripe_unit: int) -> np.ndarray:
    """An object's logical data rows [k, L]: stripe t's chunk i is row
    i's t-th stripe_unit bytes (the tail stripe zero-padded)."""
    width = k * stripe_unit
    raw = np.frombuffer(obj, dtype=np.uint8)
    pad = (-raw.size) % width
    if pad:
        raw = np.concatenate([raw, np.zeros(pad, dtype=np.uint8)])
    return raw.reshape(-1, k, stripe_unit).transpose(1, 0, 2).reshape(k, -1)


def shards(obj: bytes, code: Code, stripe_unit: int) -> np.ndarray:
    """Every shard [n, L] of an object as the code stores it."""
    return code.encode(stripe(obj, code.k, stripe_unit))
