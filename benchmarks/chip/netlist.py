"""A configuration's netlist: committed data, checked by its fingerprint.

The netlist is part of the configuration, the way published weights would
be. It is kept beside the configuration's file as ``configs/<name>.npz``:
plain arrays (``n_inputs``, ``gates`` as ``(op, a, b)`` rows, ``outputs``,
and the binarized layers it was converted from). The configuration names
the file (``netlist_file``) and its ``fingerprint``; a run refuses a file
whose fingerprint differs. The plain reference reads those arrays and
nothing else, so neither the cell's work nor the reference's answers
follow a change to the program's conversion flow.

The recipe that made a file, kept for the record (the training's float
rounding depends on the host's CPU model, so another host may make
another netlist; a changed netlist is a changed configuration):

  JAX_PLATFORMS=cpu PYTHONPATH=src:. python3 benchmarks/chip/netlist.py \\
      --config benchmarks/chip/configs/<name>.json \\
      --out benchmarks/chip/configs/<name>.npz
"""
from __future__ import annotations

import argparse
import hashlib
import importlib.util
import json
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np


@dataclass(frozen=True)
class Netlist:
    """Plain arrays of one combinational netlist (``gate_ir`` wire
    numbering: wire 0 is 0, wire 1 is 1, then inputs, then gates)."""

    n_inputs: int
    gates: np.ndarray            # (n_gates, 3) int32: opcode, src_a, src_b
    outputs: np.ndarray          # (n_outputs,) int32 wire ids
    layers: tuple                # ((W, b), ...) the binarized source layers
    fingerprint: str

    @property
    def n_gates(self) -> int:
        return int(self.gates.shape[0])

    @property
    def n_outputs(self) -> int:
        return int(self.outputs.shape[0])


def save(path: Path, n_inputs: int, gates, outputs, layers) -> None:
    arrays = {"n_inputs": np.int64(n_inputs),
              "gates": np.asarray(gates, np.int32).reshape(-1, 3),
              "outputs": np.asarray(outputs, np.int32),
              "n_layers": np.int64(len(layers))}
    for i, (w, b) in enumerate(layers):
        arrays[f"w{i}"] = np.asarray(w, np.float32)
        arrays[f"b{i}"] = np.asarray(b, np.float32)
    np.savez_compressed(path, **arrays)


def load(path: Path) -> Netlist:
    with np.load(path) as z:
        gates = z["gates"].astype(np.int32)
        outputs = z["outputs"].astype(np.int32)
        layers = tuple((z[f"w{i}"], z[f"b{i}"])
                       for i in range(int(z["n_layers"])))
        n_inputs = int(z["n_inputs"])
    h = hashlib.blake2b(digest_size=16)
    for a in (np.int64(n_inputs), gates, outputs):
        h.update(np.ascontiguousarray(a).tobytes())
    for w, b in layers:
        h.update(np.ascontiguousarray(w).tobytes())
        h.update(np.ascontiguousarray(b).tobytes())
    return Netlist(n_inputs, gates, outputs, layers, h.hexdigest())


def of_config(root: Path, config: dict) -> tuple[Netlist, float]:
    """The configuration's committed netlist and the seconds its load
    took; a file whose fingerprint is not the configuration's is refused."""
    t0 = time.perf_counter()
    nl = load(root / config["netlist_file"])
    if nl.fingerprint != config["fingerprint"]:
        raise ValueError(
            f"{config['netlist_file']}: fingerprint {nl.fingerprint}, the "
            f"configuration states {config['fingerprint']}; refusing to run")
    return nl, time.perf_counter() - t0


def load_module(path: Path):
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(
        f"_bench_{path.stem.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="make one netlist file")
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    spec = json.loads(Path(args.config).read_text())["netlist"]
    t0 = time.perf_counter()
    made = load_module(Path(__file__).resolve().parent / "converters"
                       / f"{spec['kind']}.py").build(spec)
    g = made["graph"]
    save(Path(args.out), g.n_inputs, g.gates, g.outputs, made["layers"])
    nl = load(Path(args.out))
    print(f"[netlist] {args.out}: {nl.n_gates} gates in "
          f"{time.perf_counter() - t0:.1f} s, fingerprint {nl.fingerprint}",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
