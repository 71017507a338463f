"""A binarized MLP's hidden stack, NullaNet-converted and composed.

Trains the MLP on the flow's seeded synthetic task, samples each hidden
layer's care-set from the hard forward pass, converts every hidden layer
(``flow.layer_graph``) and composes them into one netlist: the graph
``LogicClassifier.stacked_graph`` serves.
"""
from __future__ import annotations

import numpy as np


def build(p: dict) -> dict:
    from repro.core.gate_ir import compose_graphs
    from repro.core.nullanet import BinaryMLPConfig, train_binary_mlp
    from repro.flow import FlowConfig, hard_forward, input_bits, layer_graph

    hidden = tuple(p["hidden"])
    flow = FlowConfig(n_features=p["n_features"], hidden=hidden,
                      n_classes=p["n_classes"], n_samples=p["n_samples"],
                      train_steps=p["train_steps"], seed=p["seed"])
    xt, yt, _, _ = flow.load_data()
    params = train_binary_mlp(
        BinaryMLPConfig(p["n_features"], hidden, p["n_classes"],
                        seed=p["seed"]), xt, yt, steps=p["train_steps"])
    params = {k: np.asarray(v) for k, v in params.items()}
    n_layers = len(hidden) + 1
    acts, _ = hard_forward(params, input_bits(xt).astype(np.uint8), n_layers)
    graphs = [layer_graph(params[f"w{i}"], params[f"b{i}"], acts[i],
                          mode=p["mode"], name=f"layer{i}")
              for i in range(len(hidden))]
    return {"graph": compose_graphs(graphs, name="hidden-stack"),
            "layers": [(params[f"w{i}"], params[f"b{i}"])
                       for i in range(len(hidden))]}
