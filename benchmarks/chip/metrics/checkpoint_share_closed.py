"""``checkpoint_share`` in a closed-loop cell: there each caller waits for
its answer before it sends again, so time the engine spends checkpointing
is time no caller is served, and the share moves ``tokens_per_s``."""
from chipbench.metrics.checkpoint_share import read  # noqa: F401
