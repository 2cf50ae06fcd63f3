"""Model FLOP utilisation of the traced window (see ``_mfu``)."""
from chipbench.metrics._mfu import read  # noqa: F401
