"""Quickstart: compile a Boolean netlist onto the time-shared logic fabric.

    PYTHONPATH=src python examples/quickstart.py

Walks the paper's full §4/§6 flow on a small Verilog module: parse ->
logic synthesis -> levelize -> sub-kernel scheduling -> execution on the
Pallas "DSP fabric" kernel, validated against direct DAG evaluation, plus
the analytical cost model's view of the schedule.
"""
import numpy as np

from repro.core.cost_model import CostModel, FfclStats
from repro.core.levelize import levelize
from repro.core.opt import PassManager
from repro.core.scheduler import compile_graph
from repro.core.spec import CompileSpec
from repro.core.verilog import parse_verilog
from repro.kernels.logic_dsp import logic_infer_bits
from repro.launch.compile_cache import enable_compile_cache

VERILOG = """
module majority5_and_parity(a, b, c, d, e, maj, par);
  input a, b, c, d, e;
  output maj, par;
  wire ab, ac, ad, ae, bc, bd, be, cd, ce, de;
  and g0 (ab, a, b);  and g1 (ac, a, c);  and g2 (ad, a, d);
  and g3 (ae, a, e);  and g4 (bc, b, c);  and g5 (bd, b, d);
  and g6 (be, b, e);  and g7 (cd, c, d);  and g8 (ce, c, e);
  and g9 (de, d, e);
  // majority-of-5 = OR of all 3-subsets; factored via pair terms
  assign maj = (ab & (c | d | e)) | (ac & (d | e)) | (ad & e)
             | (bc & (d | e)) | (bd & e) | (cd & e);
  assign par = a ^ b ^ c ^ d ^ e;
endmodule
"""


def main() -> None:
    enable_compile_cache()
    graph = parse_verilog(VERILOG)
    print(f"parsed: {graph.stats()}")
    res = PassManager.default().run(graph)   # pass-based optimization
    graph = res.graph
    lv = levelize(graph)
    print(f"synthesized ({res.iterations} pipeline iters): {graph.stats()}  "
          f"level histogram={list(lv.histogram())}")

    # the declarative compilation target (core/spec.py): optimize="none"
    # because the pass pipeline already ran above
    spec = CompileSpec(n_unit=4, alloc="liveness", optimize="none")
    prog = compile_graph(graph, spec)
    print(f"scheduled on {spec.n_unit} units: {prog.n_steps} sub-kernel "
          f"steps, {prog.n_addr} buffer rows (paper eq. 23)")

    rng = np.random.default_rng(0)
    x = rng.integers(0, 2, (1000, 5)).astype(bool)
    got = logic_infer_bits(prog, x)          # Pallas kernel
    want = graph.evaluate(x)
    assert (got == want).all()
    maj = x.sum(axis=1) >= 3
    par = x.sum(axis=1) % 2 == 1
    assert (got[:, 0] == maj).all() and (got[:, 1] == par).all()
    print("kernel output == direct evaluation == ground truth  [1000 vectors]")

    model = CostModel()
    b = model.breakdown(FfclStats.from_graph(graph), spec.n_unit, 1000)
    print(f"cost model: {b.n_total_pipelined:.0f} cycles "
          f"(dm={b.n_data_moves:.0f}, compute={b.n_compute:.0f}, "
          f"bound={b.bound})")


if __name__ == "__main__":
    main()
