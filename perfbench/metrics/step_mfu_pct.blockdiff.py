"""The whole block step's share of the device's bf16 peak on the host's
clock: the operations of the positions the window's block steps computed
for live rows (``serving_dispatch_rowsteps_total{kind="live"}`` x the block
of four; the adapter's ``forward_flops_per_position`` of the cell's
configuration: the attention's matrices, the router, 8 experts a position,
the head, and the attention over the mean context of the window's requests,
prompt and half the answer) over the published peak (``lib/peaks.py``), as
a share of the seconds the dispatches took (``serving_dispatch_seconds``).
A width bucket's padding rows and a gathered table's dead rows are not
counted. None for a program without the block counters."""
from lib import spec
from lib.loopclock import delta, dispatches
from lib.peaks import peak

CELL = "blockdiff-saturate"
BLOCK = 4


def read(ctx):
    engine = ctx.get("engine") or {}
    rows = delta(ctx, "serving_dispatch_rowsteps_total", 'kind="live"')
    seconds = dispatches(ctx, 'path="step"')[0]
    if not delta(ctx, "serving_block_forwards_total") or not rows or not seconds:
        return None
    done = [r for r in ctx["results"] if r.get("ok")]
    if not done:
        return None
    context = sum(r["prompt_len"] + r["n_new"] / 2.0 for r in done) / len(done)
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    per_position = spec.load_model(cfg["model_type"]).forward_flops_per_position(cfg, context)
    kind = ctx.get("device_kind") or engine["device_kind"]
    return 100.0 * BLOCK * rows * per_position / peak(kind)["bf16_flops_per_s"] / seconds
