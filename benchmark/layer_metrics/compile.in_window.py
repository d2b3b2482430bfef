"""Programs built inside the measured window that should not have been: every
build (a trace, a lowering, and a compilation or a load from the compile
cache; ``jax.monitoring``) beyond the configuration's
``rebuilds_per_operation``, and at least every miss of the compile cache.  It
must be 0: anything else means the warm-up missed a shape, and also makes the
run ``correct: false``."""


def read(run):
    return run.facts.get("compiles_in_window")
