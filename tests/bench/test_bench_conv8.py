"""VGG16 conv8's configuration at its fan-in, 2304 bits, with 4 of its 512
neurons: made by the configuration's own recipe (``netlist.py``), served
through ``FrontDoor`` -> ``LogicEngine`` in interpret mode and compared
with the plain reference, and its control checked against the
calibration patterns the conversion fixed."""
import asyncio
import json

import numpy as np
import pytest

from bench_tiny import ROOT

from benchmarks.chip import harness, netlist
from benchmarks.chip.reference import Reference, control

CONFIG = json.loads(
    (ROOT / "benchmarks/chip/configs/vgg16-conv8.json").read_text())
N_NEURONS = 4
#: ragged requests of whole 4x4 images (16 samples each): with a capacity
#: of 128 samples, chunks of several requests share each wave
SIZES = (16, 48, 112, 160, 32, 80)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """The configuration with ``n_neurons`` cut to 4, made by
    ``netlist.py``; returns its configuration and netlist."""
    tmp = tmp_path_factory.mktemp("conv8")
    spec = {**CONFIG["netlist"], "n_neurons": N_NEURONS}
    cfg = tmp / "conv8-small.json"
    cfg.write_text(json.dumps({**CONFIG, "netlist": spec}))
    out = tmp / "conv8-small.npz"
    assert netlist.main(["--config", str(cfg), "--out", str(out)]) == 0
    return spec, netlist.load(out)


def test_small_conv8_has_the_published_fanin(small):
    spec, nl = small
    assert spec["fanin"] == CONFIG["fanin"] == nl.n_inputs == 2304
    assert nl.n_outputs == N_NEURONS
    (w, b), = nl.layers
    assert w.shape == (2304, N_NEURONS) and b.shape == (N_NEURONS,)


def test_served_bits_equal_the_reference(small):
    """Ragged requests through the front door and the engine, in waves
    they share, bit-exact against ``reference.py``."""
    from repro.core.spec import CompileSpec
    from repro.serve import FrontDoor
    from repro.serve.logic_engine import LogicEngine

    _, nl = small
    graph = harness.build_graph(nl, CONFIG["name"])
    rng = np.random.default_rng(2**31 + 8)
    payloads = [rng.integers(0, 2, (n, nl.n_inputs)).astype(bool)
                for n in SIZES]
    engine = LogicEngine(CompileSpec(n_unit=int(CONFIG["n_unit"])),
                         capacity=128)

    async def go():
        door = FrontDoor(engine, default_deadline_s=120.0)
        door.register("conv8", graph)
        async with door:
            return await asyncio.gather(
                *(door.submit("conv8", x) for x in payloads))

    results = asyncio.run(asyncio.wait_for(go(), timeout=300))
    want = Reference.of(nl).evaluate(np.concatenate(payloads))
    assert np.array_equal(np.concatenate(results), want)
    # fewer waves than the requests would take alone: chunks shared waves
    assert engine.invocations < sum(-(-n // 128) for n in SIZES)


def test_control_agrees_on_the_calibration_patterns_only(small):
    """The netlist is the binarized layer on the care-set the conversion
    saw (the converter's own draw: weights, bias, then the patterns) and
    differs from it off that set."""
    spec, nl = small
    rng = np.random.default_rng(spec["seed"])
    rng.normal(size=(spec["fanin"], spec["n_neurons"]))
    rng.normal(size=spec["n_neurons"])
    calib = rng.integers(0, 2, (spec["calib_patterns"], spec["fanin"]))
    ref = Reference.of(nl)
    assert np.array_equal(control(nl.layers, calib.astype(bool)),
                          ref.evaluate(calib.astype(bool)))
    off = np.random.default_rng(9).integers(0, 2, (4096, nl.n_inputs))
    wrong = np.count_nonzero(control(nl.layers, off.astype(bool))
                             != ref.evaluate(off.astype(bool)))
    assert wrong > off.shape[0] * nl.n_outputs // 10
