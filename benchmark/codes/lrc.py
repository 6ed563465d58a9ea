"""Locally repairable code, Ceph's ``plugin=lrc`` k/m/l form: the
``ErasureCodeLrc::parse_kml`` expansion into a mapping and layers (one
global layer, one local layer per group), each layer a Reed-Solomon
Vandermonde code over the chunks it names, encoded top-down."""

from __future__ import annotations

from benchmark.codes.reed_sol_van import rs_vandermonde

#: the ``<plugin>`` profiles this file is the reference for (the k/m/l
#: form takes no technique)
NAMES = ("lrc", "lrc_tpu")


def lrc_layout(k: int, m: int, l: int):
    """Ceph ErasureCodeLrc::parse_kml: (mapping, [layer chunk maps])."""
    if (k + m) % l:
        raise ValueError("k + m must be a multiple of l")
    groups = (k + m) // l
    if k % groups or m % groups:
        raise ValueError("k and m must be multiples of (k + m) / l")
    mapping = ("D" * (k // groups) + "_" * (m // groups) + "_") * groups
    layers = [("D" * (k // groups) + "c" * (m // groups) + "_") * groups]
    for i in range(groups):
        layers.append("".join(("D" * l + "c") if i == j else "_" * (l + 1)
                              for j in range(groups)))
    return mapping, layers


def layout(profile: dict):
    """(chunk count, data positions, [(chunk map, generator)])."""
    mapping, maps = lrc_layout(int(profile["k"]), int(profile["m"]),
                               int(profile["l"]))
    data = [i for i, c in enumerate(mapping) if c == "D"]
    return len(mapping), data, [
        (cm, rs_vandermonde(cm.count("D"), cm.count("c"))) for cm in maps]
