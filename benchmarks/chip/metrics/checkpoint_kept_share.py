"""Per cent of the bytes the engine's checkpoints moved from the device
that they kept: ``repro_checkpoint_bytes_total`` (the due slots' blobs)
over ``repro_checkpoint_transfer_bytes_total`` (the whole cache, each
checkpoint), both counted over the traced stretch
(``ctx["program_counters"]``).  Nothing where no checkpoint moved
anything, or the engine has no such counter."""


def read(ctx):
    counts = ctx.get("program_counters") or {}
    moved = counts.get("repro_checkpoint_transfer_bytes_total")
    if not moved:
        return None
    return 100.0 * counts.get("repro_checkpoint_bytes_total", 0.0) / moved
