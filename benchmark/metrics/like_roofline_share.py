"""The least time the chip could take for the ``LIKE`` of the window's
queries (the bytes it has to read, the configuration's ``like_bytes``: the
comments' live characters, lengths and validity, over the peak HBM bytes/s of
``peaks.json``) over the device seconds of the kernel's operations in the
traced window: those of ``ctx["trace"]["device_ops"]`` whose short names the
configuration lists (``like_ops``).  ``None`` where the configuration has no
``LIKE`` or the trace holds none of its operations."""


def read(ctx):
    mod, cfg = ctx["mod"], ctx["cfg"]
    if not hasattr(mod, "like_ops") or not ctx["records"]:
        return None
    names = set(mod.like_ops(cfg))
    busy = sum(s for name, s in ctx["trace"]["device_ops"] if name in names)
    if not busy:
        return None
    peak = ctx["peaks"][ctx["device"]["kind"]]["hbm_bytes_per_s"] \
        * ctx["chips"]
    least = len(ctx["records"]) * mod.like_bytes(cfg) / peak
    return 100.0 * least / busy
