"""Jit'd public API over the logic_dsp kernel + jnp bit packing.

Jit caching is **per program object**, not module-global: each
(frozen, immutable) :class:`LogicProgram` / :class:`MegaProgram` carries
its own runner cache (attached the same way :func:`program_arrays`
memoizes device arrays), so a program's traces are deduped against ITS
prior calls and released with the object — a module-scope ``jax.jit``
would key on stream shapes, retrace once per distinct
``(n_steps, n_unit, W)`` into a process-wide cache, and keep evicted
programs' traces alive forever.  ``trace_count()`` observes actual
retraces (the counter bumps inside the traced Python body) so tests can
pin the contract.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import calibrate as _calibrate
from repro.core.scheduler import LogicProgram, MegaProgram
from repro.kernels.logic_dsp import kernel as _k
from repro.kernels.logic_dsp.ref import logic_forward_ref

WORD_BITS = 32

_traces = 0


def _count_trace() -> None:
    global _traces
    _traces += 1


def trace_count() -> int:
    """Number of runner *traces* taken so far (bumped inside the traced
    body, so a jit cache hit does not move it)."""
    return _traces


def _runner_cache(prog) -> dict:
    """The per-program jit-runner cache, created on first use and attached
    to the (frozen) program object — same lifetime trick as the
    ``program_arrays`` memo, so traces die with the program."""
    cache = getattr(prog, "_jit_runners", None)
    if cache is None:
        cache = {}
        object.__setattr__(prog, "_jit_runners", cache)
    return cache


def pack_bits_jnp(bits: jnp.ndarray) -> jnp.ndarray:
    """(batch, n) bool -> (n, ceil(batch/32)) int32 (LSB-first), jit-safe."""
    batch, n = bits.shape
    w = -(-batch // WORD_BITS)
    pad = w * WORD_BITS - batch
    b = jnp.pad(bits.astype(jnp.uint32), ((0, pad), (0, 0)))
    chunks = b.reshape(w, WORD_BITS, n)
    weights = (jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=jnp.uint32))
    words = (chunks * weights[None, :, None]).sum(axis=1, dtype=jnp.uint32)
    return words.astype(jnp.int32).T


def unpack_bits_jnp(words: jnp.ndarray, batch: int) -> jnp.ndarray:
    """(n, W) int32 -> (batch, n) bool."""
    n, w = words.shape
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words.astype(jnp.uint32)[:, :, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(n, w * WORD_BITS).T[:batch].astype(bool)


def _pad_words(words: jnp.ndarray, block_w: int) -> jnp.ndarray:
    w = words.shape[1]
    pad = (-w) % block_w
    if pad:
        words = jnp.pad(words, ((0, 0), (0, pad)))
    return words


def program_arrays(prog: LogicProgram, pad_unit: int = 8) -> dict:
    """Program streams as device arrays, n_unit padded to sublane multiple.

    NOP padding (opcode 0, sources at row 0, dst at trash) preserves step
    homogeneity: the specialized slab op also runs on padded rows, whose
    results land on the trash address and are never read.

    The result is memoized on the (frozen, immutable) program object:
    the streams are per-program constants, and re-padding/re-uploading
    them on every inference call would sit in the hot loop.
    """
    cached = getattr(prog, "_device_arrays", None)
    if cached is not None and cached[0] == pad_unit:
        return cached[1]
    pad = (-prog.n_unit) % pad_unit

    def p(a, fill):
        a = np.asarray(a, dtype=np.int32)
        if pad:
            a = np.pad(a, ((0, 0), (0, pad)), constant_values=fill)
        return jnp.asarray(a)

    arrs = {
        "src_a": p(prog.src_a, 0), "src_b": p(prog.src_b, 0),
        "dst": p(prog.dst, prog.trash_addr), "opcode": p(prog.opcode, 0),
        "step_branch": jnp.asarray(prog.step_branch, dtype=jnp.int32),
        "output_addrs": jnp.asarray(prog.output_addrs, dtype=jnp.int32),
        "n_addr": prog.n_addr,
    }
    object.__setattr__(prog, "_device_arrays", (pad_unit, arrs))
    return arrs


def forward_words(src_a, src_b, dst, opcode, step_branch, output_addrs,
                  words: jnp.ndarray, *, n_addr: int,
                  block_w: int = _k.LANE, interpret: bool | None = None,
                  use_ref: bool = False) -> jnp.ndarray:
    """Word-level program execution: (n_inputs, W) -> (n_outputs, W) int32.

    Jit-safe core shared by :func:`logic_forward`, the fused
    :func:`logic_infer_bits` path, and the serving engine
    (serve/logic_engine.py), which amortizes one call across all queued
    samples of a batch slot table. Gateless programs (0 steps) fall back to
    the jnp reference: pallas rejects the (0, n_unit) stream block shape.
    """
    if use_ref or src_a.shape[0] == 0:
        return logic_forward_ref(src_a, src_b, dst, opcode, words,
                                 output_addrs, n_addr,
                                 step_branch=step_branch)
    # never pad a small batch out to a full lane tile: clamping the block
    # to the (sublane-rounded) word count keeps the grid at one step while
    # shrinking the padded compute (a 10-word batch runs 16 wide, not 128)
    block_w = min(block_w,
                  -(-words.shape[1] // _k.SUBLANE) * _k.SUBLANE)
    padded = _pad_words(words, block_w)
    out = _k.logic_pallas_call(
        src_a, src_b, dst, opcode, step_branch, padded, output_addrs,
        n_addr=n_addr, block_w=block_w, interpret=interpret)
    return out[:, :words.shape[1]]


def logic_forward(prog: LogicProgram, input_words: jnp.ndarray,
                  block_w: int = _k.LANE, interpret: bool | None = None,
                  use_ref: bool = False) -> jnp.ndarray:
    """Packed-word forward: (n_inputs, W) int32 -> (n_outputs, W) int32."""
    arrs = program_arrays(prog)
    return forward_words(
        arrs["src_a"], arrs["src_b"], arrs["dst"], arrs["opcode"],
        arrs["step_branch"], arrs["output_addrs"], input_words,
        n_addr=arrs["n_addr"], block_w=block_w, interpret=interpret,
        use_ref=use_ref)


def infer_runner(prog: LogicProgram, block_w: int = _k.LANE,
                 interpret: bool | None = None, use_ref: bool = False):
    """The program's fused pack -> execute -> unpack jit runner, cached ON
    the program object per kernel config.

    Keeping the bit (un)packing inside the same XLA computation as the
    kernel matters: eagerly dispatched pack/unpack around the (sub-ms)
    program execution used to dominate end-to-end latency by >10x.  The
    per-program cache (not a module-scope jit) is what lets repeat calls
    on one program — the engine-runner pattern — hit exactly one trace
    per batch shape, and lets eviction drop the traces with the program.
    """
    cache = _runner_cache(prog)
    key = ("bits", block_w, interpret, use_ref)
    fn = cache.get(key)
    if fn is None:
        arrs = program_arrays(prog)

        def run(bits):
            _count_trace()
            words = pack_bits_jnp(bits)
            out = forward_words(
                arrs["src_a"], arrs["src_b"], arrs["dst"], arrs["opcode"],
                arrs["step_branch"], arrs["output_addrs"], words,
                n_addr=arrs["n_addr"], block_w=block_w,
                interpret=interpret, use_ref=use_ref)
            return unpack_bits_jnp(out, bits.shape[0])

        fn = jax.jit(run)
        cache[key] = fn
    return fn


def logic_infer_bits(prog: LogicProgram, bits: np.ndarray | jnp.ndarray,
                     block_w: int = _k.LANE, interpret: bool | None = None,
                     use_ref: bool = False) -> np.ndarray:
    """Boolean convenience wrapper: (batch, n_inputs) -> (batch, n_outputs).

    While a :class:`~repro.core.calibrate.PhaseTimer` is active the call
    routes through :func:`phased_infer_bits` and records its per-phase
    wall-clock split on the timer; disabled (the default), the check is
    one module-attribute read — zero overhead on the fused hot path.
    """
    timer = _calibrate._ACTIVE
    if timer is not None:
        out, phases = phased_infer_bits(prog, bits, block_w=block_w,
                                        interpret=interpret, use_ref=use_ref)
        timer.record(phases, backend="ref" if use_ref else "pallas",
                     n_unit=prog.n_unit,
                     batch=int(np.asarray(bits).shape[0]))
        return out
    bits = jnp.asarray(bits, dtype=bool)
    run = infer_runner(prog, block_w=block_w, interpret=interpret,
                       use_ref=use_ref)
    return np.asarray(run(bits))


# ---------------------------------------------------------------------------
# phase-split execution (calibration measurement path, DESIGN.md §12)
# ---------------------------------------------------------------------------

def _host_streams(prog: LogicProgram, pad_unit: int = 8) -> dict:
    """The :func:`program_arrays` padding, but as HOST numpy arrays and
    memoized separately — the phased path re-uploads them every call so
    the ``setup`` phase times an actual program-stream transfer instead
    of a device-cache hit."""
    cached = getattr(prog, "_phase_host_arrays", None)
    if cached is not None and cached[0] == pad_unit:
        return cached[1]
    pad = (-prog.n_unit) % pad_unit

    def p(a, fill):
        a = np.asarray(a, dtype=np.int32)
        if pad:
            a = np.pad(a, ((0, 0), (0, pad)), constant_values=fill)
        return a

    arrs = {
        "src_a": p(prog.src_a, 0), "src_b": p(prog.src_b, 0),
        "dst": p(prog.dst, prog.trash_addr), "opcode": p(prog.opcode, 0),
        "step_branch": np.asarray(prog.step_branch, dtype=np.int32),
        "output_addrs": np.asarray(prog.output_addrs, dtype=np.int32),
    }
    object.__setattr__(prog, "_phase_host_arrays", (pad_unit, arrs))
    return arrs


def phased_infer_bits(prog: LogicProgram, bits: np.ndarray | jnp.ndarray,
                      block_w: int = _k.LANE, interpret: bool | None = None,
                      use_ref: bool = False
                      ) -> tuple[np.ndarray, dict[str, float]]:
    """One inference split into the four calibration phases.

    Returns ``(out, phases)`` where ``phases`` maps each of
    ``core.calibrate.PHASES`` to seconds, each boundary forced with
    ``block_until_ready`` so async dispatch cannot smear a phase into
    its neighbour:

        pack    H2D of the boolean batch + jitted bit packing
        setup   fresh device_put of every program stream (what the
                memoized fast path amortizes away)
        kernel  the jitted program execution over packed words
        unpack  jitted unpacking + D2H of the result

    The output is bit-identical to :func:`logic_infer_bits` (same kernel
    body, pinned by tests); only the fusion boundaries differ, which is
    why the fused runner — not this path — stays the serving hot path.
    Runners are cached per program object like :func:`infer_runner`.
    """
    cache = _runner_cache(prog)
    key = ("phases", block_w, interpret, use_ref)
    fns = cache.get(key)
    if fns is None:
        def compute(streams, words):
            _count_trace()
            return forward_words(
                streams["src_a"], streams["src_b"], streams["dst"],
                streams["opcode"], streams["step_branch"],
                streams["output_addrs"], words, n_addr=prog.n_addr,
                block_w=block_w, interpret=interpret, use_ref=use_ref)

        fns = (jax.jit(pack_bits_jnp), jax.jit(compute),
               jax.jit(unpack_bits_jnp, static_argnums=(1,)))
        cache[key] = fns
    pack_fn, compute_fn, unpack_fn = fns
    host = _host_streams(prog)
    batch = int(np.asarray(bits).shape[0])
    t = time.perf_counter

    t0 = t()
    dev_bits = jax.block_until_ready(jnp.asarray(bits, dtype=bool))
    words = jax.block_until_ready(pack_fn(dev_bits))
    t1 = t()
    streams = jax.block_until_ready(
        {k: jax.device_put(v) for k, v in host.items()})
    t2 = t()
    out_words = jax.block_until_ready(compute_fn(streams, words))
    t3 = t()
    out = np.asarray(jax.block_until_ready(unpack_fn(out_words, batch)))
    t4 = t()
    phases = {"pack": t1 - t0, "setup": t2 - t1, "kernel": t3 - t2,
              "unpack": t4 - t3}
    return out, phases


# ---------------------------------------------------------------------------
# megaprogram execution (single-launch pipelines)
# ---------------------------------------------------------------------------

def mega_arrays(mega: MegaProgram, pad_unit: int = 8) -> dict:
    """MegaProgram streams as device arrays, lanes padded to a sublane
    multiple — the NOP fill writes each step's OWN stage trash row
    (``mega.step_trash``), since stages may size their buffers
    differently and a foreign trash row could alias a live address.
    Memoized on the (frozen) mega object like :func:`program_arrays` —
    but as HOST (numpy) arrays: mega runners call this from inside their
    own trace, where a ``jnp.asarray`` result would be a tracer that must
    not leak into the memo.  Numpy streams embed as constants at trace
    time, so the jitted runner pays the upload once per trace either
    way."""
    cached = getattr(mega, "_host_arrays", None)
    if cached is not None and cached[0] == pad_unit:
        return cached[1]
    pad = (-mega.n_unit) % pad_unit

    def p(a, fill):
        a = np.asarray(a, dtype=np.int32)
        if pad:
            fill_cols = np.broadcast_to(
                np.asarray(fill, dtype=np.int32).reshape(-1, 1),
                (a.shape[0], pad))
            a = np.concatenate([a, fill_cols], axis=1)
        return a

    zeros = np.zeros(mega.total_steps, dtype=np.int32)
    arrs = {
        "src_a": p(mega.src_a, zeros), "src_b": p(mega.src_b, zeros),
        "dst": p(mega.dst, mega.step_trash),
        "opcode": p(mega.opcode, zeros),
        "step_branch": np.asarray(mega.step_branch, dtype=np.int32),
        "out_addrs": np.asarray(mega.out_addrs, dtype=np.int32),
        "perm": np.asarray(mega.output_perm, dtype=np.int32),
    }
    object.__setattr__(mega, "_host_arrays", (pad_unit, arrs))
    return arrs


def _mega_forward_ref(mega: MegaProgram, arrs: dict,
                      words: jnp.ndarray) -> jnp.ndarray:
    """jnp reference for mega execution: the per-stage
    :func:`logic_forward_ref` chain / fan-out the fused kernel replaces.
    Also the fallback when the pipeline has zero total steps (pallas
    rejects (0, n_unit) stream blocks)."""
    def stage(meta):
        step_lo, step_hi, n_in, n_out, out_lo = meta
        # slices go through jnp: logic_forward_ref's fori_loop indexes the
        # streams with a traced step counter, which numpy can't do
        def run(stage_words):
            return logic_forward_ref(
                jnp.asarray(arrs["src_a"][step_lo:step_hi]),
                jnp.asarray(arrs["src_b"][step_lo:step_hi]),
                jnp.asarray(arrs["dst"][step_lo:step_hi]),
                jnp.asarray(arrs["opcode"][step_lo:step_hi]), stage_words,
                jnp.asarray(arrs["out_addrs"][out_lo:out_lo + n_out]),
                mega.n_addr,
                step_branch=jnp.asarray(
                    arrs["step_branch"][step_lo:step_hi]))
        return run

    if mega.mode == "chain":
        h = words
        for meta in mega.stage_meta:
            h = stage(meta)(h)
        return h
    slabs = [stage(meta)(words) for meta in mega.stage_meta]
    cat = slabs[0] if len(slabs) == 1 else jnp.concatenate(slabs, axis=0)
    return jnp.take(cat, arrs["perm"], axis=0)


def mega_forward_words(mega: MegaProgram, words: jnp.ndarray, *,
                       block_w: int = _k.LANE, interpret: bool | None = None,
                       use_ref: bool = False) -> jnp.ndarray:
    """Word-level mega execution: (n_inputs, W) -> (n_outputs, W) int32 in
    ONE kernel launch (or the stage-chained jnp reference)."""
    arrs = mega_arrays(mega)
    if use_ref or mega.total_steps == 0:
        return _mega_forward_ref(mega, arrs, words)
    # same small-batch clamp as forward_words: one grid step, minimal pad
    block_w = min(block_w,
                  -(-words.shape[1] // _k.SUBLANE) * _k.SUBLANE)
    padded = _pad_words(words, block_w)
    out = _k.mega_pallas_call(
        arrs["src_a"], arrs["src_b"], arrs["dst"], arrs["opcode"],
        arrs["step_branch"], padded, arrs["out_addrs"], arrs["perm"],
        n_addr=mega.n_addr, stage_meta=mega.stage_meta,
        chain=(mega.mode == "chain"), block_w=block_w, interpret=interpret)
    return out[:, :words.shape[1]]


def mega_infer_runner(mega: MegaProgram, block_w: int = _k.LANE,
                      interpret: bool | None = None, use_ref: bool = False):
    """Fused pack -> megakernel -> unpack jit, cached on the mega object
    (one trace per batch shape per config — the single-launch analogue of
    :func:`infer_runner`)."""
    cache = _runner_cache(mega)
    key = ("bits", block_w, interpret, use_ref)
    fn = cache.get(key)
    if fn is None:
        def run(bits):
            _count_trace()
            words = pack_bits_jnp(bits)
            out = mega_forward_words(mega, words, block_w=block_w,
                                     interpret=interpret, use_ref=use_ref)
            return unpack_bits_jnp(out, bits.shape[0])

        fn = jax.jit(run)
        cache[key] = fn
    return fn


def mega_infer_bits(mega: MegaProgram, bits: np.ndarray | jnp.ndarray,
                    block_w: int = _k.LANE, interpret: bool | None = None,
                    use_ref: bool = False) -> np.ndarray:
    """Boolean convenience wrapper over the megakernel:
    (batch, n_inputs) -> (batch, n_outputs) in one launch."""
    bits = jnp.asarray(bits, dtype=bool)
    run = mega_infer_runner(mega, block_w=block_w, interpret=interpret,
                            use_ref=use_ref)
    return np.asarray(run(bits))
