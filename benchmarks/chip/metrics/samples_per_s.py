"""All samples of the requests completed in the window, over the window's
seconds (host clock)."""


def read(run):
    ok = run["done"] <= run["t_close"]
    return float(run["n"][ok].sum()) / run["window_s"]
