"""The least time the chips could take for the window's queries (the bytes
each query has to read, by the configuration's ``query_bytes``, over the peak
HBM bytes/s of ``peaks.json``, all chips together) over the time the device
was busy in the traced window.  Bound by bytes: these queries do next to no
arithmetic per byte.  The same bytes whatever implements the plan."""


def read(ctx):
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    busy = ctx["trace"]["busy_s"]
    if not ctx["records"] or not busy:
        return None
    peak = ctx["peaks"][kind]["hbm_bytes_per_s"] * ctx["chips"]
    least = len(ctx["records"]) * ctx["mod"].query_bytes(ctx["cfg"]) / peak
    return 100.0 * least / busy   # busy_s is the chips' average
