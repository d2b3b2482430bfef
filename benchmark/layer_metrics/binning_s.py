"""Host seconds the set-up fit spent binning: the ``phase.binning_s``
attribute of its ``lightgbm.train`` span (a synchronous host phase)."""


def read(run):
    return run.facts.get("binning_s")
