"""Plain reference for the served logic path, and its control.

``Reference`` evaluates a netlist's arrays gate by gate in numpy: 64
samples per uint64 word, one level of the netlist at a time, blocks of
rows so that it fits. It imports nothing of the program under test; the
opcode table below is the netlist format's numbering.

``control`` is the binarized network the netlist was converted from, put
in the netlist's place: ``(2x - 1) @ W + b >= 0`` layer by layer. The
NullaNet conversion in ISF mode fixes each neuron's function only on its
care-set, so off it the network and its netlist disagree: serving the
network breaks the guarantee that every served bit is the netlist's.
"""
from __future__ import annotations

import numpy as np

_ONES = np.uint64(0xFFFFFFFFFFFFFFFF)

#: opcode -> function of two uint64 word arrays (gate_ir numbering)
OPS = {
    0: lambda a, b: np.zeros_like(a),     # NOP
    1: lambda a, b: a & b,                # AND
    2: lambda a, b: a | b,                # OR
    3: lambda a, b: a ^ b,                # XOR
    4: lambda a, b: ~(a & b),             # NAND
    5: lambda a, b: ~(a | b),             # NOR
    6: lambda a, b: ~(a ^ b),             # XNOR
    7: lambda a, b: ~a,                   # NOT a
    8: lambda a, b: a.copy(),             # COPY a
}
UNARY = (0, 7, 8)
BLOCK_ROWS = 16384


class Reference:
    """Level-by-level evaluator of ``(n_inputs, gates, outputs)``."""

    def __init__(self, n_inputs: int, gates: np.ndarray, outputs: np.ndarray):
        gates = np.asarray(gates, np.int64).reshape(-1, 3)
        self.n_inputs = int(n_inputs)
        self.outputs = np.asarray(outputs, np.int64)
        first = 2 + self.n_inputs
        self.n_wires = first + gates.shape[0]
        level = np.zeros(self.n_wires, np.int64)
        for i, (op, a, b) in enumerate(gates.tolist()):
            if not (0 <= a < first + i and 0 <= b < first + i):
                raise ValueError(f"gate {i} reads a wire that follows it")
            la = level[a] if op in UNARY else max(level[a], level[b])
            level[first + i] = la + 1
        glevel = level[first:]
        self.plan = []                  # [(op, dst, a, b), ...] by level
        for lv in range(1, int(glevel.max(initial=0)) + 1):
            at = np.nonzero(glevel == lv)[0]
            for op in np.unique(gates[at, 0]):
                sel = at[gates[at, 0] == op]
                self.plan.append((int(op), sel + first, gates[sel, 1],
                                  gates[sel, 2]))

    @classmethod
    def of(cls, netlist) -> "Reference":
        return cls(netlist.n_inputs, netlist.gates, netlist.outputs)

    def evaluate(self, bits: np.ndarray) -> np.ndarray:
        """(n, n_inputs) bool -> (n, n_outputs) bool."""
        bits = np.asarray(bits, dtype=bool)
        if bits.ndim != 2 or bits.shape[1] != self.n_inputs:
            raise ValueError(f"inputs must be (n, {self.n_inputs}), "
                             f"got {bits.shape}")
        out = np.empty((bits.shape[0], self.outputs.shape[0]), dtype=bool)
        for lo in range(0, bits.shape[0], BLOCK_ROWS):
            out[lo:lo + BLOCK_ROWS] = self._block(bits[lo:lo + BLOCK_ROWS])
        return out

    def _block(self, bits: np.ndarray) -> np.ndarray:
        n = bits.shape[0]
        words = -(-n // 64)
        packed = np.zeros((self.n_inputs, words * 8), np.uint8)
        packed[:, :-(-n // 8)] = np.packbits(bits.T, axis=1,
                                             bitorder="little")
        vals = np.empty((self.n_wires, words), np.uint64)
        vals[0] = 0
        vals[1] = _ONES
        vals[2:2 + self.n_inputs] = packed.view("<u8")
        for op, dst, a, b in self.plan:
            vals[dst] = OPS[op](vals[a], vals[b])
        res = vals[self.outputs].astype("<u8").view(np.uint8)
        return np.unpackbits(res, axis=1, bitorder="little")[:, :n].T \
            .astype(bool)


def control(layers, bits: np.ndarray) -> np.ndarray:
    """The binarized source network on (n, n_inputs) bits, in float64."""
    bits = np.asarray(bits, dtype=bool)
    out = []
    for lo in range(0, bits.shape[0], BLOCK_ROWS):
        h = bits[lo:lo + BLOCK_ROWS]
        for w, b in layers:
            y = (2.0 * h - 1.0) @ np.asarray(w, np.float64) \
                + np.asarray(b, np.float64)
            h = y >= 0
        out.append(h)
    if not out:
        return np.zeros((0, np.asarray(layers[-1][0]).shape[1]), bool)
    return np.concatenate(out)
