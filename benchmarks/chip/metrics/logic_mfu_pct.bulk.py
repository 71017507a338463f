"""The whole served path's share of the chips' peak int32 rate: netlist
gates x samples completed in the window / 32, over window x chips x the
peak int32 rate (``peaks.json``)."""


def read(run):
    peak = run["peaks"]
    if peak is None:
        raise KeyError("the device kind is not in peaks.json")
    ok = run["done"] <= run["t_close"]
    ops = run["gates"] * float(run["n"][ok].sum()) / 32
    return ops / (run["window_s"] * run["chips"]
                  * peak["int32_ops_per_s"]) * 100
