"""Programs traced or compiled (or loaded from the compile cache) inside
the window, from jax.monitoring events; set-up should leave none."""


def compute(rec):
    return rec["window_compiles"]
