"""One binarized layer with seeded weights, NullaNet-converted (ISF).

The care-set is ``calib_patterns`` seeded random input patterns, the ISF
density ``benchmarks/workloads.py`` documents for representative neurons.
"""
from __future__ import annotations

import numpy as np


def build(p: dict) -> dict:
    from repro.flow import layer_graph

    rng = np.random.default_rng(p["seed"])
    fanin, n = p["fanin"], p["n_neurons"]
    w = rng.normal(size=(fanin, n)).astype(np.float32)
    b = (rng.normal(size=n) * 0.1).astype(np.float32)
    calib = rng.integers(0, 2, (p["calib_patterns"], fanin)).astype(np.uint8)
    graph = layer_graph(w, b, calib, mode=p["mode"], name=p.get("name",
                                                                 "layer"))
    return {"graph": graph, "layers": [(w, b)]}
