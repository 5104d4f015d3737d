"""The decode kernel's share of its roofline over the traced stretch: the
least time the chip could take for the rows the steps gave it, over the
seconds of ``retention_step*`` in the trace (``ctx["trace"]["ops"]``). Rows:
growth of ``serving_dispatch_rowsteps_total{kind="computed"}`` between
``snap_t0`` and ``snap_t1`` (the kernel runs every row of a width, a free
slot's too), times the cell's layers. The least time is a row's state read
once and written once over the HBM bandwidth, a row and layer from the
configuration (``models/brumby.py`` ``retention_step_bytes``, over the
8,256 monomials the mathematics needs: the program's 8,320 rows read
under 100, never over): the bytes bound it, its operations are a
thousandth of the peak's. None untraced, and for a program without the
kernel."""
from lib import spec
from lib.loopclock import delta
from lib.peaks import peak

CELL = "longreason-saturate"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("snap_t0") or not ctx.get("snap_t1"):
        return None
    seconds = sum(
        v[0] for name, v in trace.get("ops", {}).items()
        if name.startswith("retention_step")
    )
    # the counter's growth over the TRACED stretch, not the window
    traced = dict(ctx, snap_w0=ctx["snap_t0"], snap_w1=ctx["snap_t1"])
    rows = delta(traced, "serving_dispatch_rowsteps_total", 'kind="computed"')
    if not seconds or not rows:
        return None
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    model = spec.load_model(cfg["model_type"])
    p = peak(ctx.get("device_kind") or ctx["engine"]["device_kind"])
    moved = rows * cfg["num_hidden_layers"] * model.retention_step_bytes(cfg)
    return 100.0 * moved / p["hbm_bytes_per_s"] / seconds
