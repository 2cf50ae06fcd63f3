"""Device idle share of the traced window (see ``_idle``)."""
from chipbench.metrics._idle import read  # noqa: F401
