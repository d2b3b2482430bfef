"""Every program JAX built inside the measured window: each is a trace, a
lowering and the load of an executable on the host (or a compilation, which
``compile.in_window`` forbids).  A warmed program builds none; where it makes
a new jitted function per operation the rate pays for it, and this counts
them."""


def read(run):
    return run.facts.get("rebuilds_in_window")
