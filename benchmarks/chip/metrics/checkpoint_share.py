"""Per cent of the traced window the engine spent in its periodic and
admission checkpoints (``ServingEngine._checkpoint``: a device_get of the
whole cache and a crc32 of each slot on the host), from the engine's own
``stats["ckpt_ms"]`` counter over the window."""


def read(ctx):
    w = ctx["summary"]["window_s"]
    if w <= 0:
        return None
    return 100.0 * ctx["counters"]["ckpt_ms"] / 1e3 / w
