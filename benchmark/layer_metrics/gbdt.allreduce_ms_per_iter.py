"""Device milliseconds per boosting iteration under the ``gbdt.allreduce``
scope: the histogram all-reduce of the sharded grower (``histogram_psum``), as
far as the ``XLA Ops`` line shows it; asynchronous halves on ``Async XLA Ops``
are not read.  Own time of the traced operations whose scope path names it
(``benchmark/phase_times.py``)."""
from benchmark import phase_times


def read(run):
    return phase_times.ms_per_iter(run, "gbdt.allreduce")
