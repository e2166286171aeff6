"""``decode_step_mfu`` in a closed-loop cell: there a faster decode burst
ends each answer sooner and the caller sends its next request sooner, so
the share moves ``tokens_per_s``."""
from chipbench.metrics.decode_step_mfu import read  # noqa: F401
