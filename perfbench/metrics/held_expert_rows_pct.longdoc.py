"""Share of the routers' assignments that fell on an expert held here: 100
x ``serving_expert_rows_total{kind="held"}`` / ``{kind="routed"}`` over the
window, both counted by the program beside its expert bytes. 12.5 under
even routing over 40 of 320; a share the routers favour or shun shows
here before it shows in the rate. None for a program without the
counter."""
from lib.loopclock import delta

ROWS = "serving_expert_rows_total"


def read(ctx):
    held = delta(ctx, ROWS, 'kind="held"')
    routed = delta(ctx, ROWS, 'kind="routed"')
    if held is None or not routed:
        return None
    return 100.0 * held / routed
