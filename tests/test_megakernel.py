"""Megakernel regression suite: the single-launch whole-network executor.

Pins the three launch-chain bugfixes this feature shipped with, plus the
serving/flow integration contracts:

  * **per-program jit caching** — runner traces are cached on the program
    object (ops.py), so repeated same-shape calls take exactly ONE trace
    and distinct programs never collide in a module-global cache;
  * **gateless stages** — a 0-step stage inside a megaprogram must be a
    pure pass-through (no zero-trip ``fori_loop``, no stage-offset
    desync for the stages after it);
  * **padding hygiene** — the 32-samples/word packing and the block_w
    grid padding produce garbage lanes; chained stages must never let
    that garbage contaminate real lanes (batch 1, batch 31/33, and a
    batch that spills across grid blocks all agree with the oracle);
  * **single launch** — the fused path really is one ``pallas_call``
    (counter hook, not timing);
  * **engine chain serving** — ``serve_chain`` caches, LRU-evicts, and
    recompiles chain entries bit-exactly.
"""
import numpy as np
import pytest

from repro.core.gate_ir import CONST1, LogicGraph, random_graph
from repro.core.scheduler import (build_megaprogram, compile_graph,
                                  execute_megaprogram_np)
from repro.core.spec import CompileSpec
from repro.kernels.logic_dsp import kernel as _k
from repro.kernels.logic_dsp.ops import (mega_forward_words, mega_infer_bits,
                                         logic_infer_bits, pack_bits_jnp,
                                         trace_count, unpack_bits_jnp)

import jax.numpy as jnp


def _bits(rng, batch, n):
    return rng.integers(0, 2, (batch, n)).astype(bool)


def _layer(rng, n_in, n_gates, n_out):
    return random_graph(rng, n_in, n_gates, n_out, unary_frac=0.2,
                        locality=16)


def _chain_progs(graphs, n_unit=8, alloc="liveness"):
    spec = CompileSpec(n_unit=n_unit, alloc=alloc, optimize="none")
    return [compile_graph(g, spec) for g in graphs]


def _stack_eval(graphs, bits):
    h = np.asarray(bits, dtype=bool)
    for g in graphs:
        h = g.evaluate(h)
    return h


# ---------------------------------------------------------------------------
# satellite 1: per-program jit caching — trace-count pin
# ---------------------------------------------------------------------------

def test_runner_traces_once_per_shape():
    """Same program, same batch shape, three calls -> exactly one trace."""
    rng = np.random.default_rng(0)
    g = _layer(rng, 6, 50, 4)
    prog = compile_graph(g, CompileSpec(n_unit=8, optimize="none"))
    bits = _bits(rng, 40, 6)
    before = trace_count()
    for _ in range(3):
        out = logic_infer_bits(prog, bits)
    assert trace_count() - before == 1
    assert (out == g.evaluate(bits)).all()
    # a NEW batch shape is a legitimate retrace — exactly one more
    logic_infer_bits(prog, _bits(rng, 7, 6))
    assert trace_count() - before == 2


def test_runner_cache_is_per_program_object():
    """Two same-shape programs keep separate runners: no module-global
    cache collision, and traces die with the program object."""
    rng = np.random.default_rng(1)
    g1, g2 = _layer(rng, 5, 30, 3), _layer(rng, 5, 30, 3)
    spec = CompileSpec(n_unit=8, optimize="none")
    p1, p2 = compile_graph(g1, spec), compile_graph(g2, spec)
    bits = _bits(rng, 33, 5)
    assert (logic_infer_bits(p1, bits) == g1.evaluate(bits)).all()
    assert (logic_infer_bits(p2, bits) == g2.evaluate(bits)).all()
    assert getattr(p1, "_jit_runners") is not getattr(p2, "_jit_runners")


def test_mega_runner_traces_once_per_shape():
    rng = np.random.default_rng(2)
    graphs = [_layer(rng, 6, 40, 5), _layer(rng, 5, 30, 3)]
    mega = build_megaprogram(_chain_progs(graphs), mode="chain")
    bits = _bits(rng, 45, 6)
    before = trace_count()
    for _ in range(3):
        out = mega_infer_bits(mega, bits)
    assert trace_count() - before == 1
    assert (out == _stack_eval(graphs, bits)).all()


# ---------------------------------------------------------------------------
# satellite 2: gateless stages inside a megaprogram
# ---------------------------------------------------------------------------

def _passthrough(n):
    g = LogicGraph(n, name="pass")
    g.set_outputs([g.input_wire(i) for i in range(n)])
    return g


def test_gateless_middle_stage():
    """A 0-step pass-through between two real stages: no zero-trip loop,
    and the stage AFTER it still reads the right step/out offsets."""
    rng = np.random.default_rng(3)
    graphs = [_layer(rng, 6, 40, 4), _passthrough(4), _layer(rng, 4, 25, 3)]
    progs = _chain_progs(graphs)
    assert progs[1].n_steps == 0
    mega = build_megaprogram(progs, mode="chain")
    bits = _bits(rng, 37, 6)
    want = _stack_eval(graphs, bits)
    assert (mega_infer_bits(mega, bits, use_ref=False) == want).all()
    assert (mega_infer_bits(mega, bits, use_ref=True) == want).all()
    assert (execute_megaprogram_np(mega, bits) == want).all()


def test_gateless_edge_stages():
    """Gateless first and last stages (shuffle + const outputs survive)."""
    rng = np.random.default_rng(4)
    shuffle = LogicGraph(5, name="shuffle")
    shuffle.set_outputs([shuffle.input_wire(i) for i in (3, 1, 4, 0, 2)])
    tail = LogicGraph(3, name="tail")
    tail.set_outputs([tail.input_wire(2), CONST1, tail.input_wire(0)])
    graphs = [shuffle, _layer(rng, 5, 30, 3), tail]
    mega = build_megaprogram(_chain_progs(graphs), mode="chain")
    bits = _bits(rng, 50, 5)
    want = _stack_eval(graphs, bits)
    assert (mega_infer_bits(mega, bits, use_ref=False) == want).all()


def test_all_gateless_pipeline_routes_to_ref():
    """total_steps == 0: pallas cannot take (0, n_unit) streams; the mega
    path must fall back to the jnp reference and still be exact."""
    rng = np.random.default_rng(5)
    graphs = [_passthrough(4), _passthrough(4)]
    mega = build_megaprogram(_chain_progs(graphs), mode="chain")
    assert mega.total_steps == 0
    bits = _bits(rng, 21, 4)
    assert (mega_infer_bits(mega, bits) == bits).all()


# ---------------------------------------------------------------------------
# satellite 3: padding hygiene on the chained path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [1, 31, 32, 33, 70])
def test_chain_padding_parity(batch):
    """Word-padding garbage (inverting gates flip the zero-padded lanes)
    must stay confined to padding lanes across stage handoffs."""
    rng = np.random.default_rng(6)
    graphs = [_layer(rng, 6, 40, 5), _layer(rng, 5, 35, 4)]
    mega = build_megaprogram(_chain_progs(graphs), mode="chain")
    bits = _bits(rng, batch, 6)
    want = _stack_eval(graphs, bits)
    assert (mega_infer_bits(mega, bits) == want).all()


def test_block_spill_padding_parity():
    """A batch spanning several grid blocks (block_w=2 words): the
    _pad_words fill for the ragged last block must not leak either."""
    rng = np.random.default_rng(7)
    graphs = [_layer(rng, 6, 40, 5), _layer(rng, 5, 35, 4)]
    mega = build_megaprogram(_chain_progs(graphs), mode="chain")
    bits = _bits(rng, 5 * 32 + 3, 6)      # 6 words -> 3 blocks of 2
    want = _stack_eval(graphs, bits)
    words = pack_bits_jnp(jnp.asarray(bits))
    out = mega_forward_words(mega, words, block_w=2)
    got = np.asarray(unpack_bits_jnp(out, bits.shape[0]))
    assert (got == want).all()


# ---------------------------------------------------------------------------
# single-launch pin (counter hook, not timing)
# ---------------------------------------------------------------------------

def test_megakernel_is_single_launch():
    """One fresh megaprogram, one trace -> exactly one pallas_call, even
    for a 3-stage pipeline that used to take 3 launches."""
    rng = np.random.default_rng(8)
    graphs = [_layer(rng, 6, 40, 5), _layer(rng, 5, 30, 4),
              _layer(rng, 4, 25, 3)]
    mega = build_megaprogram(_chain_progs(graphs), mode="chain")
    bits = _bits(rng, 45, 6)
    before = _k.launch_count()
    out = mega_infer_bits(mega, bits)
    assert _k.launch_count() - before == 1
    assert (out == _stack_eval(graphs, bits)).all()
    # cached runner: further same-shape calls add ZERO launches
    mega_infer_bits(mega, bits)
    assert _k.launch_count() - before == 1


# ---------------------------------------------------------------------------
# record blocks, capacity, and the backend choice
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["mono", "chain", "parallel"])
def test_record_blocks_stream_through_both_slots(monkeypatch, mode):
    """Programs longer than one SMEM record block: with the record budget
    cut to 16-step blocks, every stage walks several blocks through both
    slots (prefetching the next while one runs) and ends on a partial
    block — and stays bit-exact."""
    monkeypatch.setattr(_k, "SMEM_RECORD_BYTES",
                        2 * 4 * 16 * _k.record_words(8))
    assert _k.block_steps(8, 1000) == 16
    rng = np.random.default_rng(20)
    if mode == "parallel":
        graphs = [_layer(rng, 6, 300, 5), _layer(rng, 6, 250, 4)]
    else:
        graphs = [_layer(rng, 6, 300, 5), _layer(rng, 5, 250, 4)]
    progs = _chain_progs(graphs)
    assert all(p.n_steps > 2 * 16 and p.n_steps % 16 for p in progs)
    bits = _bits(rng, 70, 6)
    if mode == "mono":
        got, want = logic_infer_bits(progs[0], bits), graphs[0].evaluate(bits)
    else:
        mega = build_megaprogram(progs, mode=mode)
        got, want = mega_infer_bits(mega, bits), \
            execute_megaprogram_np(mega, bits)
    assert (got == want).all()


def test_program_that_cannot_fit_raises_with_bytes():
    """An address file beyond the core's VMEM is a typed, permanent
    compile failure naming the bytes — never a silent fallback."""
    from repro.core.errors import FabricCapacityError, PermanentCompileError
    from repro.kernels.logic_dsp.ops import program_arrays
    rng = np.random.default_rng(21)
    prog = compile_graph(_layer(rng, 6, 40, 4),
                         CompileSpec(n_unit=8, optimize="none"))
    a = program_arrays(prog)
    words = jnp.zeros((6, 128), jnp.int32)
    with pytest.raises(FabricCapacityError,
                       match=r"needs \d+ bytes of VMEM") as ei:
        _k.logic_pallas_call(a["src_a"], a["src_b"], a["dst"], a["opcode"],
                             a["step_branch"], words, a["output_addrs"],
                             n_addr=300_000)
    assert isinstance(ei.value, PermanentCompileError)


def test_interpret_resolves_from_backend():
    """One helper picks compiled vs interpreted: the interpreter on a CPU
    backend, Mosaic on a TPU; an explicit choice is honoured."""
    import jax
    from repro.kernels.platform import resolve_interpret
    assert resolve_interpret() is (jax.default_backend() != "tpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


# ---------------------------------------------------------------------------
# builder validation
# ---------------------------------------------------------------------------

def test_build_megaprogram_validation():
    rng = np.random.default_rng(9)
    a = compile_graph(_layer(rng, 6, 30, 4),
                      CompileSpec(n_unit=8, optimize="none"))
    b = compile_graph(_layer(rng, 5, 30, 3),
                      CompileSpec(n_unit=8, optimize="none"))
    with pytest.raises(ValueError, match="at least one stage"):
        build_megaprogram([])
    with pytest.raises(ValueError, match="width mismatch"):
        build_megaprogram([a, b], mode="chain")     # 4 outs != 5 ins
    with pytest.raises(ValueError, match="no output permutation"):
        build_megaprogram([a], mode="chain",
                          output_perm=np.arange(4))
    with pytest.raises(ValueError, match="mode"):
        build_megaprogram([a], mode="fanout")


def test_parallel_mode_permutation():
    """Parallel mode applies the partition permutation in-kernel."""
    rng = np.random.default_rng(10)
    g1 = _layer(rng, 6, 30, 2)
    g2 = _layer(rng, 6, 25, 2)
    p1, p2 = _chain_progs([g1, g2])
    perm = np.array([2, 0, 3, 1], dtype=np.int64)   # interleave the slabs
    mega = build_megaprogram([p1, p2], mode="parallel", output_perm=perm)
    bits = _bits(rng, 41, 6)
    cat = np.concatenate([g1.evaluate(bits), g2.evaluate(bits)], axis=1)
    want = cat[:, perm]
    assert (mega_infer_bits(mega, bits) == want).all()
    assert (execute_megaprogram_np(mega, bits) == want).all()


# ---------------------------------------------------------------------------
# mega lane padding: mixed n_unit stages
# ---------------------------------------------------------------------------

def test_mixed_n_unit_stages_lane_padded():
    """Stages scheduled at different n_unit concatenate by padding the
    narrow stage's lanes with NOPs into its OWN trash row."""
    rng = np.random.default_rng(11)
    g1, g2 = _layer(rng, 6, 40, 5), _layer(rng, 5, 35, 4)
    p1 = compile_graph(g1, CompileSpec(n_unit=8, optimize="none"))
    p2 = compile_graph(g2, CompileSpec(n_unit=64, optimize="none"))
    mega = build_megaprogram([p1, p2], mode="chain")
    assert mega.n_unit == 64
    bits = _bits(rng, 39, 6)
    want = _stack_eval([g1, g2], bits)
    assert (mega_infer_bits(mega, bits, use_ref=False) == want).all()
    assert (execute_megaprogram_np(mega, bits) == want).all()


# ---------------------------------------------------------------------------
# engine chain serving
# ---------------------------------------------------------------------------

def test_engine_serve_chain_bit_exact_and_cached():
    from repro.serve import LogicEngine
    rng = np.random.default_rng(12)
    graphs = [_layer(rng, 6, 40, 5), _layer(rng, 5, 30, 3)]
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=64)
    bits = _bits(rng, 150, 6)           # > capacity: 3 chunks, 1 launch/wave
    want = _stack_eval(graphs, bits)
    assert (eng.serve_chain(graphs, bits) == want).all()
    misses = eng.cache.misses
    assert (eng.serve_chain(graphs, bits) == want).all()
    assert eng.cache.misses == misses   # second serve is a cache hit
    assert eng.cache.hits >= 1


def test_engine_serve_chain_evict_recompile():
    """An LRU-evicted chain entry recompiles transparently mid-queue."""
    from repro.serve import LogicEngine
    from repro.serve.logic_engine import ProgramCache
    rng = np.random.default_rng(13)
    chain_a = [_layer(rng, 6, 40, 5), _layer(rng, 5, 30, 3)]
    chain_b = [_layer(rng, 6, 35, 4), _layer(rng, 4, 25, 2)]
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=64,
                      cache=ProgramCache(max_entries=1))
    bits = _bits(rng, 40, 6)
    assert (eng.serve_chain(chain_a, bits)
            == _stack_eval(chain_a, bits)).all()
    assert (eng.serve_chain(chain_b, bits)
            == _stack_eval(chain_b, bits)).all()     # evicts chain_a
    assert (eng.serve_chain(chain_a, bits)
            == _stack_eval(chain_a, bits)).all()     # recompiles
    assert eng.cache.compiles >= 3


def test_engine_serve_chain_validates_width():
    from repro.serve import LogicEngine
    rng = np.random.default_rng(14)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=64)
    g = _layer(rng, 6, 30, 4)
    with pytest.raises(ValueError):
        eng.serve_chain([g], _bits(rng, 10, 5))      # 5 bits vs 6 inputs
    with pytest.raises(ValueError):
        eng.submit_chain([], _bits(rng, 10, 6))      # empty stage list
    with pytest.raises(ValueError):
        eng.cache.get_chain([g], CompileSpec(n_unit="auto"))


# ---------------------------------------------------------------------------
# flow classifier megakernel backend
# ---------------------------------------------------------------------------

def test_classifier_megakernel_backend_matches_reference():
    from repro.flow.classifier import build_classifier
    from repro.flow.report import FlowConfig
    from repro.core.nullanet import BinaryMLPConfig, train_binary_mlp
    from repro.flow.classifier import input_bits
    cfg = FlowConfig(n_samples=400, train_steps=30, hidden=(6, 5))
    xt, yt, xv, _ = cfg.load_data()
    mcfg = BinaryMLPConfig(n_features=cfg.n_features, hidden=cfg.hidden,
                           n_classes=cfg.n_classes, seed=cfg.seed)
    params = train_binary_mlp(mcfg, xt, yt, steps=cfg.train_steps)
    params = {k: np.asarray(v) for k, v in params.items()}
    clf = build_classifier(params, len(cfg.hidden) + 1, xt, cfg.spec)
    bits = input_bits(xv)
    ref = clf.hidden_bits(bits, backend="reference")
    got = clf.hidden_bits(bits, backend="megakernel")
    assert (got == ref).all()
    assert clf.megaprogram.n_stages == len(clf.layers)
