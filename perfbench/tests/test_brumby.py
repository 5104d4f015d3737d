"""The brumby adapter (``models/brumby.py``) and its cell,
``longreason-saturate``: pinned digests of the seeded weights, the counts
against a hand count at the published size, every catalog key in the file,
the reference's blocks of queries against the whole sum, a token hundreds of
positions back still moving the logits, the cell rehearsed tiny on the CPU
with its ways of going wrong (the int8 control, a served token moved by
one), and the six readers on hand-written scrapes."""

import hashlib
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as runner
from lib import spec
from test_rehearsal import BENCH, CPU, rehearse
from tiny import tiny_cell

FIXTURE = spec.BENCH_DIR / "tests/fixtures/brumby.weights.sha256.json"
CELL = "longreason-saturate"
CONFIG = spec.BENCH_DIR / "configs/brumby-14b-base.json"
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def _digest(leaf) -> str:
    a = np.asarray(leaf)
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()


def _digests(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): _digest(leaf) for path, leaf in flat}


@pytest.fixture(scope="module")
def model():
    return spec.load_model("brumby")


@pytest.fixture(scope="module")
def cfg():
    return tiny_cell(CELL)["config"]


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_seeded_weights_are_pinned_and_the_two_forms_hold_the_same_values(model, cfg, seed):
    pinned = json.loads(FIXTURE.read_text())["seeds"][str(seed)]
    for dtype in ("bfloat16", "float32"):
        w = model.make_weights(seed, cfg, dtype)
        assert _digests(w) == pinned[f"make_weights.{dtype}"]
        assert {x.dtype.name for x in jax.tree.leaves(w)} == {dtype}
    params = model.make_program_params(seed, cfg, "bfloat16")
    assert _digests(model.to_reference(params, cfg)) == pinned["make_weights.bfloat16"]
    layer = params["layers"][1]
    assert layer["wq"].shape == (64, 64) and layer["wk"].shape == (64, 32)
    assert layer["wg"].shape == (64, 2) and layer["bg"].shape == (2,)
    spans = 1.0 / (1.0 - jax.nn.sigmoid(layer["bg"].astype(jnp.float32)))
    assert 15.0 < float(spans.min()) and float(spans.max()) < 8300.0


def test_counts_match_a_hand_count_at_the_published_size(model):
    cfg = json.loads(CONFIG.read_text())
    layer = (
        5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120 + 5120 * 8 + 256
        + 3 * 5120 * 17408 + 2 * 5120
    )
    assert layer == model.layer_params(cfg) == 330_352_896
    total = 8 * layer + 2 * 151_936 * 5120 + 5120
    deploy = cfg["deployment"]
    assert model.param_count(cfg) == total == deploy["parameters"] == 4_198_652_928
    assert model.weights_bytes(cfg) == 2 * total == deploy["weights_bytes"]
    assert model.gate_biases(cfg) == deploy["gate_biases"] == 64
    # all 40 layers: the model's name
    assert round((40 * layer + 2 * 151_936 * 5120 + 5120) / 1e8) == 148
    assert model.monomials(cfg) == 128 * 129 // 2 == 8256
    state = 8 * 8 * 8256 * 129 * 4
    assert model.state_bytes_per_slot(cfg) == state == deploy["state_bytes_per_slot"] == 272_646_144
    held = 8 * 8 * 65 * 128 * 129 * 4
    assert deploy["state_bytes_per_slot_held"] == held == 274_759_680
    assert deploy["env"]["PYGRID_KV_BUDGET"] == 20 * held
    assert deploy["env"]["PYGRID_SERVING_SLOTS"] == 20
    assert model.kv_bytes_per_token(cfg) == deploy["kv_bytes_per_token"] == 0
    assert deploy["served_context"] == 4096 + 1024
    # the kernels' counts, a position (a live row) and layer
    assert model.retention_chunk_flops(cfg) == 48 * 2 * 8256 * 128 + 40 * 2 * 128 * 128
    assert model.retention_chunk_bytes(cfg) == 4 * (2 * 5120 + 2 * 1024 + 8)
    assert model.retention_step_bytes(cfg) == 2 * 8 * 8256 * 129 * 4
    mats = layer - 256 - 2 * 5120
    want = 2 * 5120 * 151_936 + 8 * (2 * mats + 48 * 2 * 8256 * 128)
    assert model.forward_flops_per_position(cfg) == want
    assert model.forward_flops_per_position(cfg, 3000.0) == want  # no context in it
    assert 7.6e9 < want < 7.7e9
    dense = 2 * (8 * layer + 151_936 * 5120 + 5120)
    assert model.dense_step_bytes(cfg) == dense
    assert model.decode_step_bytes(cfg, 20) == dense + 40 * state
    with pytest.raises(NotImplementedError, match="no cell trains"):
        model.train_loss({}, False)
    with pytest.raises(ValueError, match="attention_bias"):
        model.make_weights(0, dict(cfg, attention_bias=True), "bfloat16")


def test_every_catalog_key_is_in_the_file_and_one_is_cut():
    cfg = json.loads(CONFIG.read_text())
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["reduced"] == ["num_hidden_layers"] == list(cfg["reduced_why"])
    assert cfg["num_hidden_layers"] == 8
    deploy = cfg["deployment"]
    assert deploy["published"] == {"num_hidden_layers": 40}
    assert deploy["pipeline_stages"] * cfg["num_hidden_layers"] == 40
    assert deploy["chips_a_layer"] == 1
    assert {"degree", "gate", "gate_bias", "eps", "rotary", "state_dtype"} <= set(cfg["assumed"])
    entry = next(c for c in BENCH["configs"] if c["name"] == "brumby-14b-base")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and "8 layers of 40: host share over a deployment's" in cell["why"]
    tr = tiny_cell.__globals__["spec"].cell(BENCH, CELL)["traffic"]
    assert (tr["callers"], tr["loop"]) == (32, "closed")
    assert (tr["prompt_len"]["lo"], tr["prompt_len"]["hi"]) == (512, 4096)
    assert (tr["n_new"]["lo"], tr["n_new"]["hi"]) == (256, 1024)


def test_blocks_of_queries_are_the_whole_sum_and_a_far_token_still_counts(model, cfg):
    """512 positions run in two blocks of 256 queries; the same weights over
    the first 224 (one block) give the same logits there. A token changed
    at position 0 moves the logits 400 positions on: some heads hold a
    prompt's start, which only the carried state can bring a decode step."""
    w = model.make_weights(1, cfg, "float32")
    seq = np.random.default_rng(1).integers(0, 128, (1, 512))
    a = np.asarray(model.logits(w, jnp.asarray(seq), cfg))[0]
    short = np.asarray(model.logits(w, jnp.asarray(seq[:, :224]), cfg))[0]
    assert model._query_block(512) == 256 and model._query_block(224) == 32
    assert np.abs(a[:224] - short).max() <= 2e-5
    other = seq.copy()
    other[0, 0] = (other[0, 0] + 1) % 128
    b = np.asarray(model.logits(w, jnp.asarray(other), cfg))[0]
    assert np.abs(a[400] - b[400]).max() > 1e-4
    assert np.abs(a[0] - b[0]).max() > 0.1  # and of course at once


def test_the_cell_rehearses_and_its_layers_read():
    """``test_rehearsal.py``'s walk for this cell, with every metric that
    only a traced run on the chip can read allowed to go unread."""
    said = []
    cell, run = rehearse(CELL, seed=2**31 + 77, log=said.append)
    assert run["verdict"]["correct"] is True, run["verdict"]
    assert run["compiles_in_window"] == 0 and run["failed"] == 0 and run["attempted"] > 0
    assert {c["name"]: c["value"] for c in run["verdict"]["compared"]}["malformed_answers"] == 0
    assert {r["door"] for r in run["ctx"]["results"]} == {"ws", "http"}
    line = runner.result_line(BENCH, cell, run, CPU, trace=False)
    assert set(line["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    ctx = dict(run["ctx"], device_kind="TPU v5 lite")
    layer = spec.read_per_layer(BENCH, CELL, ctx)
    named = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", CELL)}
    unread = set(named) - set(layer)
    assert all(
        named[m]["source"] == "device_trace" or m.startswith("hbm_peak_gb")
        for m in unread
    ), unread
    assert {
        "retention_step_roofline_pct.longreason", "retention_chunk_roofline_pct.longreason",
    } <= unread
    # no pool to read: the two readers of it are not this cell's
    assert not {"kv_pool_peak_pct", "kv_read_share_pct.sat"} & set(named)
    published = ("decode_hbm_floor_pct.longreason", "step_mfu_pct.longreason")
    for key, m in layer.items():
        assert math.isfinite(m["value"]) and m["value"] >= 0.0
        if "_pct" in key and key not in published:
            assert m["value"] <= 100.0, key
    assert layer["dispatch_ahead_pct.sat"]["value"] > 50.0
    assert layer["prefill_padding_pct.longreason"]["value"] > 0.0
    row = run["ctx"]["engine"]
    assert row["kv_block_bytes"] == 0 and row["kv_blocks_total"] == 0
    assert row["state_bytes_per_slot"] > 0


def test_the_control_and_a_moved_token_come_out_not_correct():
    cell, run = rehearse(CELL, seed=7, control=True)
    check = cell["traffic"]["check"]
    assert run["verdict"]["correct"] is True
    control = run["verdict"]["control"]
    # the control has to fail one of the cell's limits, not each
    assert (
        control["served_gap_max"] > check["gap_max_limit"]
        or control["served_gap_mean"] > check["gap_mean_limit"]
    )

    def move_tokens(engine):
        emit = engine._emit

        def emit_other(slot, row, tok):
            return emit(slot, row, (tok + 1) % 128)

        engine._emit = emit_other

    cell, broken = rehearse(CELL, seed=7, engine_hook=move_tokens)
    assert broken["verdict"]["correct"] is False
    over = {c["name"] for c in broken["verdict"]["compared"] if c["value"] > c["limit"]}
    assert "served_gap_max" in over
    assert runner.result_line(BENCH, cell, broken, CPU, False)["correct"] is False


# ── the readers, on hand-written scrapes ─────────────────────────────────


def _snap(true, padded, prefill_s, prefills, chunks, steps, step_s, live):
    p = "pygrid_serving_"
    return {
        p + 'prefill_tokens_total{kind="true"}': float(true),
        p + 'prefill_tokens_total{kind="padded"}': float(padded),
        p + "prefill_seconds_sum": float(prefill_s),
        p + "prefill_seconds_count": float(prefills),
        p + 'retention_chunks_total{kind="true"}': float(chunks),
        p + 'retention_chunks_total{kind="computed"}': float(chunks),
        p + 'dispatch_seconds_sum{path="step",width="20"}': float(step_s),
        p + 'dispatch_seconds_count{path="step",width="20"}': float(steps),
        p + 'dispatch_rowsteps_total{kind="live"}': float(live),
        p + 'dispatch_rowsteps_total{kind="computed"}': float(20 * steps),
        "engine": {"device_kind": "TPU v5 lite", "kv_block_bytes": 0},
    }


def test_readers_on_hand_written_scrapes(model):
    cfg = json.loads(CONFIG.read_text())
    w0 = _snap(1000, 2000, 1.0, 2, 10, 20, 0.5, 300)
    # the window: 10 prompts of 3,000 true tokens in 4,096-buckets (24
    # chunks each), 100 steps of 19 live rows
    w1 = _snap(1000 + 30_000, 2000 + 40_960, 1.0 + 2.0, 2 + 10, 10 + 240,
               20 + 100, 0.5 + 4.0, 300 + 1900)
    ctx = {
        "kind": "serve", "seconds": 51.0, "snap_w0": w0, "snap_w1": w1, "engine": w1["engine"],
        "results": [{"ok": True, "prompt_len": 3000, "n_new": 100}, {"ok": False}],
        "trace": None, "snap_t0": None, "snap_t1": None,
    }
    read = lambda name: spec.load_reader(name)(ctx)  # noqa: E731
    assert read("prefill_padding_pct.longreason") == pytest.approx(100 * (1 - 30_000 / 40_960))
    assert read("prefill_ms_per_ktok.longreason") == pytest.approx(2000.0 / 30.0)
    moved = 100 * model.dense_step_bytes(cfg) + 2 * 1900 * 272_646_144
    assert read("decode_hbm_floor_pct.longreason") == pytest.approx(100 * moved / 819e9 / 4.0)
    ops = (30_000 + 1900) * model.forward_flops_per_position(cfg)
    assert read("step_mfu_pct.longreason") == pytest.approx(100 * ops / 197e12 / 6.0)
    assert read("retention_step_roofline_pct.longreason") is None  # untraced
    assert read("retention_chunk_roofline_pct.longreason") is None
    traced = dict(ctx, snap_t0=w0, snap_t1=w1, trace={"ops": {
        "retention_step": [2.4, 800], "retention_chunk": [0.3, 80],
        "fusion_f32_64": [3.0, 10],
    }})
    # every row of the width, eight layers: the state read once, written once
    floor = 2000 * 8 * 2 * 34_080_768 / 819e9
    assert spec.load_reader("retention_step_roofline_pct.longreason")(traced) == pytest.approx(
        100 * floor / 2.4
    )
    # 240 chunks of 128 positions, eight layers; the operations bound it
    positions = 240 * 128 * 8
    by_ops = positions * model.retention_chunk_flops(cfg) / 197e12
    assert by_ops > positions * model.retention_chunk_bytes(cfg) / 819e9
    assert spec.load_reader("retention_chunk_roofline_pct.longreason")(traced) == pytest.approx(
        100 * by_ops / 0.3
    )
    # the parent of the PR that added the family has no chunk counter and
    # no kernel, a training cell no engine: nothing to read, nothing raised
    bare = {k: v for k, v in w1.items() if "retention" not in k and "prefill_tokens" not in k}
    parent = dict(traced, snap_w0=bare, snap_w1=bare, snap_t0=bare, snap_t1=bare,
                  trace={"ops": {"fusion_f32_64": [3.0, 10]}})
    for name in (m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".longreason")):
        assert spec.load_reader(name)(parent) is None, name
        assert spec.load_reader(name)({"kind": "train"}) is None, name
