"""Every cell at a size the CPU can hold: the committed cell with its
widths, depth and traffic shrunk. Only the self-tests use this."""

from __future__ import annotations

import copy

from lib import spec

TINY = dict(n_layer=2, n_embd=64, n_head=4, n_inner=256, vocab_size=128, n_positions=128)


def tiny_cell(name: str) -> dict:
    cell = copy.deepcopy(spec.cell(spec.load_benchmark(), name))
    cell["config"].update(TINY)
    tr = cell["traffic"]
    if tr["kind"] == "serve":
        deploy = cell["config"]["deployment"]
        deploy["env"] = {
            "PYGRID_SERVING_SLOTS": 4, "PYGRID_SERVING_QUEUE": 64, "PYGRID_KV_BLOCK": 16,
        }
        deploy["weights_dtype"] = "float32"
        tr.update(
            prompt_len={"dist": "log_uniform", "lo": 8, "hi": 32},
            n_new={"dist": "log_uniform", "lo": 4, "hi": 16},
            lead_in_s=1, drain_s=20, trace_s=1,
        )
        if tr["loop"] == "open":
            tr.update(rate_per_s=6, workers=8)
        else:
            tr.update(callers=8, grid=16, cycles=600)
        # float32 weights and cache on the CPU: the engine is exact there
        tr["check"].update(gap_max_limit=1e-4, gap_mean_limit=1e-5)
    else:
        tr.update(clients=2, batch=2, seq_len=64, ce_chunk=64, trace_s=0.5)
        tr["check"].update(ref_chunk=2, loss_gap_limit=2e-3, param_change_gap_limit=0.05)
    return cell
