"""The share of the device's bf16 peak that the whole of this cell's device
work reached, on the host's clock: the operations of every position the
window computed for a request (the prefills' TRUE prompt tokens,
``serving_prefill_tokens_total{kind="true"}``, without the head, which a
prefill computes for its last position alone, once a prefill; and the
decode steps' live rows, ``serving_dispatch_rowsteps_total{kind="live"}``,
with it; the adapter's ``forward_flops_per_position`` of the cell's
configuration at the window's mean context) over the published peak, as a
share of the seconds the prefills and the dispatches took
(``serving_prefill_seconds`` + ``serving_dispatch_seconds``: no second is
counted twice, and with the device never idle they are its time). A
bucket's padding and a width's free rows are not counted. None for a
program without the counters."""
from lib import spec
from lib.loopclock import delta, dispatches
from lib.peaks import peak
from lib.readers import hist_delta

CELL = "agent-saturate"


def read(ctx):
    engine = ctx.get("engine") or {}
    prompt = delta(ctx, "serving_prefill_tokens_total", 'kind="true"')
    rows = delta(ctx, "serving_dispatch_rowsteps_total", 'kind="live"')
    routed = delta(ctx, "serving_expert_rows_total", 'kind="routed"')
    if prompt is None or rows is None or not routed:
        return None
    prefill_s, prefills = hist_delta(
        ctx["snap_w0"], ctx["snap_w1"], "serving_prefill_seconds"
    )
    seconds = dispatches(ctx)[0] + prefill_s
    done = [r for r in ctx["results"] if r.get("ok")]
    if not seconds or not done:
        return None
    cfg = spec.cell(spec.load_benchmark(), CELL)["config"]
    flops = spec.load_model(cfg["model_type"]).forward_flops_per_position
    # a prompt position attends over half its prompt on average, a decode
    # row over its prompt and half its answer
    p_mean = sum(r["prompt_len"] for r in done) / len(done)
    n_mean = sum(r["n_new"] for r in done) / len(done)
    head = flops(cfg, 0.0) - flops(cfg, 0.0, head=False)
    ops = (
        prompt * flops(cfg, p_mean / 2.0, head=False) + prefills * head
        + rows * flops(cfg, p_mean + n_mean / 2.0)
    )
    kind = ctx.get("device_kind") or engine["device_kind"]
    return 100.0 * ops / peak(kind)["bf16_flops_per_s"] / seconds
