"""Compiles for a described TPU v5e chip: the main path's kernels at the
widths of the LeNet-5 classifier head, with Mosaic rather than the
interpreter.

Nothing here runs: the TPU compiler is installed and compiles for a chip
that is described, not attached.  A compile that passes proves the
kernel lowers (no gather or scatter on a value, aligned slices, VMEM and
SMEM within bounds) and that the program carries a ``tpu_custom_call``.
The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.gate_ir import random_graph
from repro.core.scheduler import build_megaprogram, compile_graph
from repro.core.spec import CompileSpec
from repro.kernels.logic_dsp.ops import (forward_words, mega_forward_words,
                                         program_arrays)
from repro.kernels.xnor_gemm import kernel as xnor_kernel

SPEC = CompileSpec(n_unit=32, optimize="none")
W = 128                                   # one 128-lane word block


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any refusal skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to a persistent cache but
    # can never be read back without the chip: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def lenet_programs():
    """fc1-sized (400 -> 120, ~52k gates) and fc2-sized (120 -> 84,
    ~16.5k gates) random programs at n_unit 32."""
    rng = np.random.default_rng(0)
    fc1 = compile_graph(random_graph(rng, 400, 52083, 120), SPEC)
    fc2 = compile_graph(random_graph(rng, 120, 16516, 84), SPEC)
    return fc1, fc2


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _words(one_chip, n):
    return jax.ShapeDtypeStruct((n, W), jnp.int32, sharding=one_chip)


def test_monolithic_kernel_compiles_for_v5e(one_chip, lenet_programs):
    fc1, _ = lenet_programs
    assert fc1.n_steps > 1000 and fc1.n_addr > 500
    a = program_arrays(fc1)

    def run(words):
        return forward_words(a["src_a"], a["src_b"], a["dst"], a["opcode"],
                             a["step_branch"], a["output_addrs"], words,
                             n_addr=a["n_addr"], interpret=False)

    assert "tpu_custom_call" in _compiled_text(run, _words(one_chip, 400))


@pytest.mark.parametrize("mode", ["chain", "parallel"])
def test_megakernel_compiles_for_v5e(one_chip, lenet_programs, mode):
    fc1, fc2 = lenet_programs
    if mode == "chain":
        stages = [fc1, fc2]
    else:               # a second output cone over the same 400 inputs
        rng = np.random.default_rng(1)
        stages = [fc1, compile_graph(random_graph(rng, 400, 16516, 84),
                                     SPEC)]
    mega = build_megaprogram(stages, mode=mode)

    def run(words):
        return mega_forward_words(mega, words, interpret=False)

    assert "tpu_custom_call" in _compiled_text(run, _words(one_chip, 400))


def test_xnor_gemm_compiles_for_v5e(one_chip):
    """VGG16 conv8: K = 3*3*256 = 2304 fan-in bits, N = 512 filters, 28x28
    output pixels (M = 784, padded to 896); K words padded to one
    lane-dense 128-word block."""
    k_bits, n, m = 2304, 512, 896
    a = jax.ShapeDtypeStruct((m, 128), jnp.int32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((n, 128), jnp.int32, sharding=one_chip)

    def run(a, b):
        return xnor_kernel.xnor_gemm_pallas(a, b, k_bits=k_bits,
                                            interpret=False)

    assert "tpu_custom_call" in _compiled_text(run, a, b)
