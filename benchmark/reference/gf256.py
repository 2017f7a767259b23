"""Plain GF(2^8) reference encoders for the benchmark's codec profiles.

Written from the published constructions, independent of the program:

- isa ``reed_sol_van``: ISA-L ``gf_gen_rs_matrix`` (erasure_code/
  ec_base.c): parity row i, column j is (2^i)^j.
- jerasure ``reed_sol_van``: ``reed_sol_vandermonde_coding_matrix``
  (jerasure reed_sol.c): the extended Vandermonde matrix made systematic
  by column operations, then scaled so the first coding row and column
  are all ones.
- lrc ``k, m, l``: upstream ``ErasureCodeLrc::parse_kml`` layers, each a
  jerasure ``reed_sol_van`` code over the chunks its layer string names.

Both use the polynomial 0x11d.  Encoding is a byte-wise table lookup
and XOR, one coded row at a time.
"""

from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF(2^8) division by 0")
    if a == 0:
        return 0
    return int(EXP[(LOG[a] - LOG[b]) % 255])


# MUL[c] is the 256-entry table of c * x
MUL = np.array([[mul(c, x) for x in range(256)] for c in range(256)],
               dtype=np.uint8)


def isa_rs_matrix(k: int, m: int) -> np.ndarray:
    out = np.zeros((m, k), dtype=np.int64)
    gen = 1
    for i in range(m):
        p = 1
        for j in range(k):
            out[i, j] = p
            p = mul(p, gen)
        gen = mul(gen, 2)
    return out


def jerasure_rs_matrix(k: int, m: int) -> np.ndarray:
    rows, cols = k + m, k
    d = np.zeros((rows, cols), dtype=np.int64)
    d[0, 0] = 1
    d[rows - 1, cols - 1] = 1
    for i in range(1, rows - 1):
        x = 1
        for j in range(cols):
            d[i, j] = x
            x = mul(x, i)
    for i in range(1, cols):
        j = i
        while j < rows and d[j, i] == 0:
            j += 1
        if j >= rows:
            raise ValueError("no pivot")
        if j != i:
            d[[i, j]] = d[[j, i]]
        if d[i, i] != 1:
            inv = div(1, int(d[i, i]))
            for r in range(rows):
                d[r, i] = mul(inv, int(d[r, i]))
        for j in range(cols):
            e = int(d[i, j])
            if j != i and e:
                for r in range(rows):
                    d[r, j] ^= mul(e, int(d[r, i]))
    for j in range(cols):
        if d[cols, j] != 1:
            inv = div(1, int(d[cols, j]))
            for r in range(cols, rows):
                d[r, j] = mul(inv, int(d[r, j]))
    for i in range(cols + 1, rows):
        if d[i, 0] != 1:
            inv = div(1, int(d[i, 0]))
            for j in range(cols):
                d[i, j] = mul(int(d[i, j]), inv)
    return d[cols:]


def lrc_layers(k: int, m: int, l: int) -> tuple[str, list[str]]:
    """(mapping, layer strings) as ``ErasureCodeLrc::parse_kml`` builds
    them; every layer uses the jerasure reed_sol_van default."""
    if (k + m) % l:
        raise ValueError("k + m must be a multiple of l")
    groups = (k + m) // l
    if k % groups or m % groups:
        raise ValueError("k and m must be multiples of (k + m) / l")
    kg, mg = k // groups, m // groups
    mapping = ("D" * kg + "_" * mg + "_") * groups
    layers = [("D" * kg + "c" * mg + "_") * groups]
    for i in range(groups):
        layers.append("".join("D" * l + "c" if i == j else "_" * (l + 1)
                              for j in range(groups)))
    return mapping, layers


def matmul(matrix: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """coded[i] = XOR_j matrix[i, j] * rows[j] over GF(2^8)."""
    out = np.zeros((matrix.shape[0], rows.shape[1]), dtype=np.uint8)
    for i in range(matrix.shape[0]):
        for j in range(matrix.shape[1]):
            c = int(matrix[i, j])
            if c == 1:
                out[i] ^= rows[j]
            elif c:
                out[i] ^= MUL[c][rows[j]]
    return out


def encode(profile: dict, data: np.ndarray) -> np.ndarray:
    """All n chunks in chunk-position order for ``data`` [k, N] uint8.

    ``profile`` is the configuration's codec profile: plugin ``isa`` or
    ``jerasure`` with ``technique=reed_sol_van``, or ``lrc`` with k, m, l.
    """
    plugin = profile["plugin"]
    k, m = int(profile["k"]), int(profile["m"])
    if data.shape[0] != k or data.dtype != np.uint8:
        raise ValueError(f"data must be [{k}, N] uint8")
    if plugin in ("isa", "jerasure"):
        if profile.get("technique", "reed_sol_van") != "reed_sol_van":
            raise ValueError("reference covers reed_sol_van only")
        build = isa_rs_matrix if plugin == "isa" else jerasure_rs_matrix
        return np.concatenate([data, matmul(build(k, m), data)])
    if plugin != "lrc":
        raise ValueError(f"no reference for plugin {plugin!r}")
    mapping, layers = lrc_layers(k, m, int(profile["l"]))
    chunks = np.zeros((len(mapping), data.shape[1]), dtype=np.uint8)
    chunks[[i for i, c in enumerate(mapping) if c == "D"]] = data
    for layer in layers:
        din = [i for i, c in enumerate(layer) if c == "D"]
        cout = [i for i, c in enumerate(layer) if c == "c"]
        chunks[cout] = matmul(jerasure_rs_matrix(len(din), len(cout)),
                              chunks[din])
    return chunks


def data_positions(profile: dict) -> list[int]:
    k = int(profile["k"])
    if profile["plugin"] != "lrc":
        return list(range(k))
    mapping, _ = lrc_layers(k, int(profile["m"]), int(profile["l"]))
    return [i for i, c in enumerate(mapping) if c == "D"]
