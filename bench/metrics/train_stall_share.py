"""Share of the window the serving thread spent blocked in
``end_slice`` (replay SGD + A^-1 rebuild, with ``max_train_lag`` 0)."""


def read(run):
    lay = run["layer"]
    return lay["router_s"]["end_slice"] / lay["window_s"] * 100.0
