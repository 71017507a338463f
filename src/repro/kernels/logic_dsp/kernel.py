"""Pallas TPU kernel: the "DSP fabric" — levelized gate-program executor.

Maps the paper's hardware architecture (Fig. 3) onto a TPU core:

  BRAM data buffer     -> VMEM scratch ``buf`` (n_addr rows x Wb lanes int32)
  Addr./Opcode buffers -> per-step program records in HBM, copied a block
                          of steps at a time into a double-buffered SMEM
                          scratch (whole streams of a real network do not
                          fit the 1 MiB of SMEM)
  DSP registers        -> (n_unit, Wb) VMEM staging tiles: per step, the
                          2 x n_unit operand rows are read with dynamic
                          single-row loads, the step's bitwise op runs on
                          the whole tile, and the n_unit result rows are
                          stored back with dynamic single-row stores
  48-lane DSP SIMD     -> 32 samples/int32 x Wb lanes per row
  URAM double buffer   -> the Pallas grid pipeline: while block g computes,
                          Mosaic DMAs block g+1's input slab HBM->VMEM
                          (paper §5.2.2/§5.2.3 made structural)

Step semantics (``scheduler.execute_program_np``, ``core/verify.py``):
every read of a step happens before any of its writes, and when two lanes
of a step write the same row the last lane wins.

Opcode dispatch is *banked* (DESIGN.md §1.2): the scheduler emits a per-step
branch index (``LogicProgram.step_branch``); homogeneous steps — the common
case after opcode sorting — run ONE specialized bitwise slab op selected by
``jax.lax.switch``; a mixed step broadcasts its per-lane opcodes into the
result tile and pays the 8-way chained select. Step fusion further shrinks
the step-loop trip count (DESIGN.md §1.3).

Grid: one dimension over batch-word blocks (Wb = 128 lanes each). The whole
program executes per block; blocks are independent (batch parallelism), so
the paper's "multiple parallel accelerators" (§5.2.4) appear as grid steps
here and as shard_map shards across chips.

The kernel compiles with Mosaic on a TPU and runs in the Pallas
interpreter on a CPU (``repro.kernels.platform.resolve_interpret``).
Nothing in it gathers or scatters on a value: Mosaic lowers neither.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.errors import FabricCapacityError
from repro.core.gate_ir import MIXED_DISPATCH
from repro.kernels.logic_dsp.ref import STEP_BRANCHES, apply_opcode_jnp
from repro.kernels.platform import resolve_interpret

LANE = 128      # lane tile (int32)
SUBLANE = 8     # sublane tile

#: per-step record: [branch, 7 x pad, src_a | src_b | dst | opcode] with
#: n_unit lanes each; the 8-word head keeps a record a multiple of 8 words
#: so a block of 16k steps is a whole number of 128-word SMEM rows
REC_HEAD = 8
#: SMEM bytes given to the two record slots (SMEM holds 1 MiB in all)
SMEM_RECORD_BYTES = 256 * 1024
#: SMEM bytes the scalar-prefetched output tables may take
SMEM_TABLE_BYTES = 256 * 1024
#: VMEM a fabric launch may ask for: the 128 MiB of a v5e/v6e core, less
#: room for Mosaic's own internal scratch
VMEM_CAP_BYTES = 100 * 1024 * 1024
#: Mosaic's default scoped-VMEM limit; larger launches raise it explicitly
VMEM_DEFAULT_BYTES = 16 * 1024 * 1024
_VMEM_HEADROOM = 2 * 1024 * 1024

_BANKED = STEP_BRANCHES[:MIXED_DISPATCH]

# ---------------------------------------------------------------------------
# launch accounting (counter hook, not timing)
# ---------------------------------------------------------------------------

_launches = 0


def _count_launch() -> None:
    global _launches
    _launches += 1


def launch_count() -> int:
    """Number of ``pl.pallas_call`` invocations *issued* so far.

    The counter increments in the Python body of the launch wrappers, so
    under ``jax.jit`` it counts launches **per trace** (the compiled
    computation replays exactly those launches on every execution) and in
    eager mode once per call.  The benchmark harness pins the megakernel
    row with it: one fresh trace of the fused runner must move the counter
    by exactly 1, whereas the chained per-layer path moves it once per
    stage.
    """
    return _launches


# ---------------------------------------------------------------------------
# sizing
# ---------------------------------------------------------------------------

def _rows(n: int) -> int:
    return -(-max(n, 1) // SUBLANE) * SUBLANE


def record_words(n_unit: int) -> int:
    """int32 words per step record."""
    return REC_HEAD + 4 * n_unit


def block_steps(n_unit: int, max_stage_steps: int) -> int:
    """Steps per SMEM record block: the largest power of two >= 16 whose
    two slots fit :data:`SMEM_RECORD_BYTES`, and no larger than the
    longest stage needs."""
    fit = SMEM_RECORD_BYTES // (2 * 4 * record_words(n_unit))
    blk = max(16, 1 << max(fit, 1).bit_length() - 1)
    need = max(16, pl.next_power_of_2(max(max_stage_steps, 1)))
    return min(blk, need)


def fabric_vmem_bytes(*, n_addr: int, n_unit: int, n_inputs: int,
                      n_outputs: int, n_hold: int, block_w: int) -> int:
    """VMEM one launch holds: the address file, three staging tiles, the
    stage hand-off slab, and the double-buffered input/output blocks —
    rows padded to the sublane tile, lanes to the lane tile."""
    lanes = -(-block_w // LANE) * LANE
    rows = (_rows(n_addr) + 3 * _rows(n_unit) + _rows(n_hold)
            + 2 * (_rows(n_inputs) + _rows(n_outputs)))
    return 4 * lanes * rows


def _check_fits(*, vmem: int, smem_records: int, smem_tables: int) -> None:
    if vmem > VMEM_CAP_BYTES:
        raise FabricCapacityError(
            f"fabric launch needs {vmem} bytes of VMEM; a core offers "
            f"{VMEM_CAP_BYTES}")
    if smem_records + smem_tables > SMEM_RECORD_BYTES + SMEM_TABLE_BYTES:
        raise FabricCapacityError(
            f"fabric launch needs {smem_records + smem_tables} bytes of "
            f"SMEM ({smem_records} for step records, {smem_tables} for "
            f"output tables); the kernel budgets "
            f"{SMEM_RECORD_BYTES + SMEM_TABLE_BYTES}")


def _records(src_a, src_b, dst, opcode, step_branch, stage_meta, blk: int):
    """Pack the streams into per-step records and pad every stage to whole
    blocks of ``blk`` steps, so each block copy is one aligned HBM row.

    Returns ``((n_blocks, blk * R) int32, first block of each stage)``.
    Traceable: the monolithic path passes jit arguments here.
    """
    n_steps = src_a.shape[0]
    rec = jnp.concatenate([
        jnp.asarray(step_branch, jnp.int32).reshape(n_steps, 1),
        jnp.zeros((n_steps, REC_HEAD - 1), jnp.int32),
        jnp.asarray(src_a, jnp.int32), jnp.asarray(src_b, jnp.int32),
        jnp.asarray(dst, jnp.int32), jnp.asarray(opcode, jnp.int32),
    ], axis=1)
    chunks, first, n_blocks = [], [], 0
    for (lo, hi, *_) in stage_meta:
        first.append(n_blocks)
        if hi == lo:
            continue
        k = -(-(hi - lo) // blk)
        chunks.append(jnp.pad(rec[lo:hi], ((0, k * blk - (hi - lo)), (0, 0))))
        n_blocks += k
    if not chunks:                  # every stage gateless: one idle block
        chunks, n_blocks = [jnp.zeros((blk, rec.shape[1]), jnp.int32)], 1
    blocks = jnp.concatenate(chunks, axis=0).reshape(n_blocks, 1, -1)
    return blocks, tuple(first)


# ---------------------------------------------------------------------------
# the fabric kernel
# ---------------------------------------------------------------------------

def _fabric_kernel(out_addrs_ref, perm_ref, rec_hbm, inputs_ref, out_ref,
                   buf, sa, sb, sr, hold, rec, sem, *,
                   stage_meta: tuple, first_block: tuple, chain: bool,
                   n_unit: int, blk: int, unrolled: bool):
    """One grid step: run every stage of the pipeline over one batch-word
    block, the address file ``buf`` resident in VMEM.

    The stage loop is a *static* Python loop over ``stage_meta``
    (``(step_lo, step_hi, n_inputs, n_outputs, out_lo)`` per stage); a
    stage walks its record blocks, prefetching block j+1 into the other
    SMEM slot while block j runs.  A gateless stage (``step_hi ==
    step_lo``) runs no loop at all.

    Every stage starts from a freshly initialized address file (zeros,
    const-1 row, inputs from row 2): the liveness allocator may reuse
    const or input rows as gate destinations, so stage k's final file is
    not a valid initial state for stage k+1.  Chain mode gathers stage
    k's output rows into ``hold``, which becomes stage k+1's input;
    parallel mode gathers every stage's outputs into ``hold`` and
    permutes them into ``out_ref`` through ``perm_ref`` at the end.
    """
    wb = buf.shape[1]
    r = record_words(n_unit)
    off_a, off_b = REC_HEAD, REC_HEAD + n_unit
    off_d, off_o = REC_HEAD + 2 * n_unit, REC_HEAD + 3 * n_unit

    def lanes(body):
        # unrolled for Mosaic (static staging rows); rolled for the
        # interpreter, whose trace and compile time grow with the body
        if unrolled:
            for i in range(n_unit):
                body(i)
            return

        def loop(i, carry):
            body(i)
            return carry

        jax.lax.fori_loop(0, n_unit, loop, 0)

    def run_steps(slot, n_steps: int):
        def step(s, carry):
            o = s * r

            def row(field, i):          # the buffer row lane i names
                return pl.ds(rec[slot, 0, o + field + i], 1)

            def read(i):                        # every read first ...
                sa[pl.ds(i, 1), :] = buf[row(off_a, i), :]
                sb[pl.ds(i, 1), :] = buf[row(off_b, i), :]

            def opcode(i):
                sr[pl.ds(i, 1), :] = jnp.full(
                    (1, wb), rec[slot, 0, o + off_o + i], jnp.int32)

            def write(i):               # ... then writes, last lane wins
                buf[row(off_d, i), :] = sr[pl.ds(i, 1), :]

            lanes(read)
            branch = rec[slot, 0, o]

            @pl.when(branch == MIXED_DISPATCH)
            def _mixed():
                lanes(opcode)
                sr[...] = apply_opcode_jnp(sr[...], sa[...], sb[...])

            @pl.when(branch != MIXED_DISPATCH)
            def _banked():
                sr[...] = jax.lax.switch(branch, _BANKED, sa[...], sb[...],
                                         None)

            lanes(write)
            return carry

        jax.lax.fori_loop(0, n_steps, step, 0)

    def copy(block, slot):
        return pltpu.make_async_copy(rec_hbm.at[block], rec.at[slot],
                                     sem.at[slot])

    def run_stage(n: int, b0: int):
        n_blk = -(-n // blk)
        copy(b0, 0).start()

        def block(j, carry):
            slot = j % 2
            copy(b0 + j + 1, 1 - slot).start()
            copy(b0 + j, slot).wait()
            run_steps(slot, blk)
            return carry

        jax.lax.fori_loop(0, n_blk - 1, block, 0)
        last = (n_blk - 1) % 2
        copy(b0 + n_blk - 1, last).wait()
        run_steps(last, n - (n_blk - 1) * blk)

    def gather(dst_ref, dst_lo: int, out_lo: int, n_out: int):
        def row(j, carry):
            dst_ref[pl.ds(dst_lo + j, 1), :] = \
                buf[pl.ds(out_addrs_ref[out_lo + j], 1), :]
            return carry

        jax.lax.fori_loop(0, n_out, row, 0)

    last_stage = len(stage_meta) - 1
    for k, (step_lo, step_hi, n_in, n_out, out_lo) in enumerate(stage_meta):
        stage_in = hold[pl.ds(0, n_in), :] if chain and k else inputs_ref[...]
        buf[...] = jnp.zeros(buf.shape, jnp.int32)
        buf[pl.ds(1, 1), :] = jnp.full((1, wb), -1, jnp.int32)  # const-1 row
        buf[pl.ds(2, n_in), :] = stage_in
        if step_hi > step_lo:          # static; gateless stage: no loop
            run_stage(step_hi - step_lo, first_block[k])
        if not chain:
            gather(hold, out_lo, out_lo, n_out)
        elif k == last_stage:
            gather(out_ref, 0, out_lo, n_out)
        else:
            gather(hold, 0, out_lo, n_out)

    if not chain:
        def place(j, carry):
            out_ref[pl.ds(j, 1), :] = hold[pl.ds(perm_ref[j], 1), :]
            return carry

        jax.lax.fori_loop(0, out_ref.shape[0], place, 0)


def _launch(src_a, src_b, dst, opcode, step_branch, input_words, out_addrs,
            perm, *, n_addr: int, stage_meta: tuple, chain: bool,
            block_w: int, interpret: bool | None):
    _count_launch()
    interpret = resolve_interpret(interpret)
    n_inputs, w = input_words.shape
    n_outputs = perm.shape[0] if not chain else stage_meta[-1][3]
    if w % block_w:
        raise ValueError(f"W={w} must be a multiple of block_w={block_w}")
    n_unit = src_a.shape[1]
    blk = block_steps(n_unit, max(hi - lo for lo, hi, *_ in stage_meta))
    blocks, first_block = _records(src_a, src_b, dst, opcode, step_branch,
                                   stage_meta, blk)
    if chain:
        n_hold = max([m[3] for m in stage_meta[:-1]], default=1)
    else:
        n_hold = sum(m[3] for m in stage_meta)
    vmem = fabric_vmem_bytes(n_addr=n_addr, n_unit=n_unit, n_inputs=n_inputs,
                             n_outputs=n_outputs, n_hold=n_hold,
                             block_w=block_w)
    _check_fits(vmem=vmem, smem_records=2 * 4 * blk * record_words(n_unit),
                smem_tables=4 * (out_addrs.shape[0] + perm.shape[0]))

    def io(g, *_):
        return (0, g)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(w // block_w,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((n_inputs, block_w), io)],
        out_specs=pl.BlockSpec((n_outputs, block_w), io),
        scratch_shapes=[
            pltpu.VMEM((n_addr, block_w), jnp.int32),     # address file
            pltpu.VMEM((n_unit, block_w), jnp.int32),     # operand a
            pltpu.VMEM((n_unit, block_w), jnp.int32),     # operand b
            pltpu.VMEM((n_unit, block_w), jnp.int32),     # result
            pltpu.VMEM((n_hold, block_w), jnp.int32),     # stage hand-off
            pltpu.SMEM((2, 1, blocks.shape[2]), jnp.int32),  # record slots
            pltpu.SemaphoreType.DMA((2,)),
        ])
    return pl.pallas_call(
        functools.partial(_fabric_kernel, stage_meta=stage_meta,
                          first_block=first_block, chain=chain,
                          n_unit=n_unit, blk=blk, unrolled=not interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_outputs, w), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=min(VMEM_CAP_BYTES,
                                 max(VMEM_DEFAULT_BYTES,
                                     vmem + _VMEM_HEADROOM))),
        interpret=interpret,
        name="logic_fabric",
    )(jnp.asarray(out_addrs, jnp.int32), jnp.asarray(perm, jnp.int32),
      blocks, input_words)


def logic_pallas_call(src_a, src_b, dst, opcode, step_branch, input_words,
                      output_addrs, *, n_addr: int, block_w: int = LANE,
                      interpret: bool | None = None):
    """Launch the kernel over ceil(W / block_w) batch-word blocks.

    Deliberately NOT jit-wrapped at module scope: a global jit cache keys
    traces on the stream *shapes*, so every distinct (n_steps, n_unit, W)
    program retraces into one process-wide cache that outlives program
    eviction and that ``ops.program_arrays``'s per-program memo cannot
    dedupe.  Callers jit per program instead (``ops.logic_infer_bits``'s
    per-program runner cache, the engine's per-entry runners), so traces
    live and die with the program.

    Args:
      src_a/src_b/dst/opcode: (n_steps, n_unit) int32 (n_unit % 8 == 0
        recommended for sublane alignment; scheduler pads with NOPs).
      step_branch: (n_steps,) int32 per-step dispatch branch
        (opcode for homogeneous steps, MIXED_DISPATCH for mixed ones).
      input_words: (n_inputs, W) int32; W padded to block_w by the caller.
      output_addrs: (n_outputs,) int32.
      interpret: ``None`` resolves from the backend
        (:func:`~repro.kernels.platform.resolve_interpret`).
    Returns:
      (n_outputs, W) int32.
    Raises:
      FabricCapacityError: the program's address file or records do not
        fit the core's VMEM/SMEM.
    """
    n_steps = src_a.shape[0]
    n_outputs = output_addrs.shape[0]
    meta = ((0, n_steps, input_words.shape[0], n_outputs, 0),)
    return _launch(src_a, src_b, dst, opcode, step_branch, input_words,
                   output_addrs, np.zeros(1, np.int32), n_addr=n_addr,
                   stage_meta=meta, chain=True, block_w=block_w,
                   interpret=interpret)


def mega_pallas_call(src_a, src_b, dst, opcode, step_branch, input_words,
                     out_addrs, perm, *, n_addr: int, stage_meta: tuple,
                     chain: bool, block_w: int = LANE,
                     interpret: bool | None = None):
    """Launch the megakernel: the whole stage pipeline per grid step.

    Args mirror :func:`logic_pallas_call` with the streams concatenated
    along the step axis (``MegaProgram``), plus the static per-stage
    offset table, the flattened per-stage output addresses, and the
    output permutation (identity in chain mode).  Like the monolithic
    wrapper it is not jit-wrapped here — callers key the trace per
    MegaProgram object.
    """
    return _launch(src_a, src_b, dst, opcode, step_branch, input_words,
                   out_addrs, perm, n_addr=n_addr, stage_meta=stage_meta,
                   chain=chain, block_w=block_w, interpret=interpret)
