"""The solar_open2 adapter (``models/solar_open2.py``) and its cell,
``longdoc-saturate``: pinned digests of the seeded weights, the counts
against a hand count at the published size, every catalog key in the file,
the delta rule's memory alone carrying a token 256 positions, the cell
rehearsed tiny on the CPU with its ways of going wrong (the int8 control, a
served token moved by one), and the seven readers on hand-written scrapes."""

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

FIXTURE = spec.BENCH_DIR / "tests/fixtures/solar_open2.weights.sha256.json"
CELL = "longdoc-saturate"
CONFIG = spec.BENCH_DIR / "configs/solar-open2-250b.json"


def _digest(leaf) -> str:
    a = np.asarray(leaf)
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()


def _digests(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): _digest(leaf) for path, leaf in flat}


@pytest.fixture(scope="module")
def model():
    return spec.load_model("solar_open2")


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
    softmax, delta = params["layers"][0], params["layers"][1]
    assert "conv_w" not in softmax and softmax["wg"].shape == (64, 64)
    assert delta["conv_w"].shape == (4, 192) and delta["A_log"].shape == (4,)
    # 2 of the router's 16 experts held, and the shared one beside them
    assert delta["router"].shape == (64, 16) and delta["w_gate"].shape == (2, 64, 32)
    assert delta["s_down"].shape == (32, 64)
    ref = model.to_reference(params, cfg)["layers"][1]
    assert ref["conv_k"].shape == (64, 4)
    assert (np.asarray(ref["conv_k"], np.float32).T == np.asarray(delta["conv_w"], np.float32)[:, 64:128]).all()


def test_counts_match_a_hand_count_at_the_published_size(model):
    cfg = json.loads(CONFIG.read_text())
    softmax = 4096 * 8192 + 2 * 4096 * 1024 + 4096 * 8192 + 8192 * 4096 + 2 * 4096
    delta = (
        3 * 4096 * 8192 + 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
        + 3 * 8192 * 4 + 64 + 8192 + 128 + 2 * 4096
    )
    assert (softmax, delta) == (109_060_096, 137_740_480)
    expert = model.expert_params(cfg)
    assert expert == 3 * 4096 * 1280 == 15_728_640
    every = 4096 * 320 + expert  # the router and the shared expert
    assert model.dense_layer_params(cfg, 0) == softmax + every
    assert model.dense_layer_params(cfg, 1) == delta + every
    assert model.layer_params(cfg, 0) == softmax + every + 40 * expert == 755_245_056
    assert model.layer_params(cfg, 3) == delta + every + 40 * expert == 783_925_440
    total = 755_245_056 + 3 * 783_925_440 + 2 * 24_576 * 4096 + 4096
    deploy = cfg["deployment"]
    assert model.param_count(cfg) == total == deploy["parameters"] == 3_308_352_064
    assert model.weights_bytes(cfg) == 2 * total == deploy["weights_bytes"]
    # all 48 layers with every expert and the whole vocabulary: the name
    whole = 12 * (softmax + every + 320 * expert) + 36 * (delta + every + 320 * expert)
    whole += 2 * 196_608 * 4096 + 4096
    assert round(whole / 1e8) == 2503
    assert model.kv_bytes_per_token(cfg) == deploy["kv_bytes_per_token"] == 4096
    state = 3 * (64 * 128 * 128 * 4 + 3 * 24_576 * 2)
    assert model.state_bytes_per_slot(cfg) == state == deploy["state_bytes_per_slot"] == 13_025_280
    block = 64 * 4096
    assert deploy["env"]["PYGRID_KV_BUDGET"] == 64 * state + (64 * 132 + 1) * block
    assert deploy["served_context"] == 132 * 64 == 8192 + 256
    # the kernel's counts, a position and layer
    assert model.kda_chunk_flops(cfg) == 64 * (6 * 128 * 128 + 4 * 64 * 128)
    assert model.kda_chunk_bytes(cfg) == 4 * (5 * 8192 + 64)
    assert model.expert_ffn_bytes(cfg, 3) == 3 * 2 * expert
    assert model.expert_ffn_flops(cfg, 100) == 200 * expert
    # a position: 2 a matmul parameter (the mixer, the router, the shared
    # expert, one routed expert at the even share), the head once
    mix0 = softmax - 2 * 4096
    mix1 = delta - (3 * 8192 * 4 + 64 + 8192 + 128 + 2 * 4096)
    want = (
        2 * (mix0 + every + expert) + 4 * 8192 * 300
        + 3 * (2 * (mix1 + every + expert) + model.kda_chunk_flops(cfg))
        + 2 * 4096 * 24_576
    )
    assert model.forward_flops_per_position(cfg, 300) == want
    assert 1.5e9 < want < 1.6e9  # ~1.54 GFLOP a position at a context of 300
    dense = 2 * (softmax + 3 * delta + 4 * every + 24_576 * 4096 + 4096)
    assert model.dense_step_bytes(cfg) == dense
    assert model.decode_step_bytes(cfg, 64, 1000, 128) == (
        dense + 128 * 2 * expert + 2 * 64 * state + 1000 * 4096
    )
    with pytest.raises(NotImplementedError, match="no cell trains"):
        model.train_loss({}, False)
    with pytest.raises(ValueError, match="use_rope"):
        model.make_weights(0, dict(cfg, use_rope=True), "bfloat16")


def test_every_catalog_key_is_in_the_file_and_three_are_cut():
    cfg = json.loads(CONFIG.read_text())
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {
            "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None,
        },
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": 8,
    }
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": (48, 4), "n_routed_experts": (320, 40), "vocab_size": (196608, 24576)}
    assert cfg["reduced"] == list(cut) and set(cfg["reduced_why"]) == set(cut)
    for key, (was, now) in cut.items():
        assert cfg[key] == now and cfg["deployment"]["published"][key] == was
    deploy = cfg["deployment"]
    assert deploy["router_experts"] == 320 and deploy["chips_a_layer"] == 8
    assert deploy["chips_a_layer"] * cfg["n_routed_experts"] == 320
    assert deploy["pipeline_stages"] * cfg["num_hidden_layers"] == 48
    entry = next(c for c in BENCH["configs"] if c["name"] == "solar-open2-250b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and "4 layers of 48: host share over a deployment's" in cell["why"]


def test_a_token_256_positions_back_moves_the_logits_through_the_delta_rule_alone(model, cfg):
    """With the softmax layer's output projection zeroed only the matrix
    state carries position 0 to position 256: a lost state shows."""
    w = model.make_weights(1, cfg, "float32")
    w = dict(w, layers=[
        dict(lw, wo=jnp.zeros_like(lw["wo"])) if "wg" in lw else lw for lw in w["layers"]
    ])
    seq = np.random.default_rng(1).integers(0, 128, (1, 288))
    other = seq.copy()
    other[0, 0] = (other[0, 0] + 1) % 128
    a = np.asarray(model.logits(w, jnp.asarray(seq), cfg))[0]
    b = np.asarray(model.logits(w, jnp.asarray(other), cfg))[0]
    assert np.abs(a[256] - b[256]).max() > 1e-4
    assert np.abs(a[0] - b[0]).max() > 0.1  # and of course at once


def test_an_expert_takes_as_many_rounds_as_its_positions_need(model, cfg):
    """The reference gathers an expert's positions a round at a time: a
    round of 8 and a round of all 64 give one answer, also where every
    position goes to the same two held experts (as a sequence's padding
    does)."""
    w = model.make_weights(2, cfg, "float32")
    seq = jnp.asarray(np.random.default_rng(2).integers(0, 128, (1, 64)))
    one_way = dict(w, layers=[
        dict(lw, router=jnp.zeros_like(lw["router"]).at[:, :2].set(1.0)) for lw in w["layers"]
    ])
    real = model.ROUND
    try:
        for weights in (w, one_way):
            model.ROUND = real
            whole = np.asarray(model.logits(weights, seq, cfg))
            model.ROUND = 8
            assert np.abs(np.asarray(model.logits(weights, seq, cfg)) - whole).max() <= 1e-5
    finally:
        model.ROUND = real
    assert np.abs(whole - np.asarray(model.logits(w, seq, cfg))).max() > 1e-3


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
    # what only the chip can say: the device's trace and its memory
    assert all(
        named[m]["source"] == "device_trace" or m.startswith("hbm_peak_gb")
        for m in unread
    ), unread
    assert {"kda_chunk_roofline_pct.longdoc", "expert_ffn_roofline_pct.longdoc"} <= unread
    # two readers take the PUBLISHED configuration's bytes and operations a
    # step (the cell's file): over a tiny engine's seconds they are finite
    # and mean nothing
    published = ("decode_hbm_floor_pct.longdoc", "step_mfu_pct.longdoc")
    for key, m in layer.items():
        assert math.isfinite(m["value"]) and m["value"] >= 0.0
        if "_pct" in key and key not in published:
            assert m["value"] <= 100.0, key
    # 2 of 16 experts held: an eighth of the assignments under even routing
    assert 5.0 <= layer["held_expert_rows_pct.longdoc"]["value"] <= 25.0
    assert layer["kv_read_share_pct.sat"]["value"] == 100.0  # the gather
    assert layer["dispatch_ahead_pct.sat"]["value"] > 50.0
    assert layer["prefill_padding_pct.longdoc"]["value"] > 0.0


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


def _snap(true, padded, prefill_s, prefills, step_bytes, prefill_bytes, routed, held,
          state, pages, steps, step_s, live):
    p = "pygrid_serving_"
    return {
        p + 'prefill_tokens_total{kind="true"}': float(true),
        p + 'prefill_tokens_total{kind="padded"}': float(padded),
        p + "prefill_seconds_sum": float(prefill_s),
        p + "prefill_seconds_count": float(prefills),
        p + 'expert_bytes_total{kind="read",path="step"}': float(step_bytes),
        p + 'expert_bytes_total{kind="read",path="prefill"}': float(prefill_bytes),
        p + 'expert_rows_total{kind="routed"}': float(routed),
        p + 'expert_rows_total{kind="held"}': float(held),
        p + 'state_bytes_total{kind="read"}': float(state),
        p + 'state_bytes_total{kind="written"}': float(state),
        p + 'kv_pages_total{kind="read"}': float(pages),
        p + 'kv_pages_total{kind="table"}': float(pages),
        p + 'dispatch_seconds_sum{path="step",width="64"}': float(step_s),
        p + 'dispatch_seconds_count{path="step",width="64"}': float(steps),
        p + 'dispatch_rowsteps_total{kind="live"}': float(live),
        p + 'dispatch_rowsteps_total{kind="computed"}': float(64 * steps),
        "engine": {"device_kind": "TPU v5 lite", "kv_block_bytes": 262_144},
    }


def test_readers_on_hand_written_scrapes(model):
    cfg = json.loads(CONFIG.read_text())
    expert = 2 * 15_728_640
    state = 13_025_280
    w0 = _snap(1000, 2000, 1.0, 2, 10 * expert, 20 * expert, 8000, 1000, 5 * state, 100, 20, 0.5, 1000)
    # the window: 10 prompts of 3,000 true tokens in 4,096-buckets, 100
    # steps of 64 live rows
    positions = 40_960 + 6400
    w1 = _snap(
        1000 + 30_000, 2000 + 40_960, 1.0 + 2.0, 2 + 10, (10 + 12_000) * expert,
        (20 + 1600) * expert, 8000 + 4 * 8 * positions, 1000 + 4 * positions,
        (5 + 6400) * state, 100 + 300_000, 20 + 100, 0.5 + 2.0, 1000 + 6400,
    )
    ctx = {
        "kind": "serve", "seconds": 51.0, "snap_w0": w0, "snap_w1": w1, "engine": w1["engine"],
        "results": [{"ok": True, "prompt_len": 3000, "n_new": 100}, {"ok": False}],
        "trace": None, "snap_t0": None, "snap_t1": None,
    }
    read = lambda name: spec.load_reader(name)(ctx)  # noqa: E731
    assert read("held_expert_rows_pct.longdoc") == pytest.approx(12.5)
    assert read("prefill_padding_pct.longdoc") == pytest.approx(100 * (1 - 30_000 / 40_960))
    assert read("prefill_ms_per_ktok.longdoc") == pytest.approx(2000.0 / 30.0)
    moved = (
        100 * model.dense_step_bytes(cfg) + 12_000 * expert + 2 * 6400 * state
        + 300_000 * 262_144
    )
    assert read("decode_hbm_floor_pct.longdoc") == pytest.approx(100 * moved / 819e9 / 2.0)
    ops = (
        30_000 * model.forward_flops_per_position(cfg, 1500.0, 1.0)
        + 6400 * model.forward_flops_per_position(cfg, 3050.0, 1.0)
    )
    assert read("step_mfu_pct.longdoc") == pytest.approx(100 * ops / 197e12 / 4.0)
    assert read("kda_chunk_roofline_pct.longdoc") is None  # untraced
    assert read("expert_ffn_roofline_pct.longdoc") is None
    traced = dict(ctx, snap_t0=w0, snap_t1=w1, trace={"ops": {
        "kda_chunk": [1.5, 30], "grouped_expert_ffn": [0.4, 440],
        "grouped_expert_ffn_f32_2048_4096": [0.1, 5], "fusion_f32_64": [3.0, 10],
    }})
    # three delta-rule layers over the padded positions; the bytes bound it
    floor = 3 * 40_960 * model.kda_chunk_bytes(cfg) / 819e9
    assert floor > 3 * 40_960 * model.kda_chunk_flops(cfg) / 197e12
    assert spec.load_reader("kda_chunk_roofline_pct.longdoc")(traced) == pytest.approx(
        100 * floor / 1.5
    )
    # the larger of the bytes' floor and the operations' floor
    by_bytes = (12_000 + 1600) * expert / 819e9
    by_ops = model.expert_ffn_flops(cfg, 4 * positions) / 197e12
    assert by_bytes > by_ops
    assert spec.load_reader("expert_ffn_roofline_pct.longdoc")(traced) == pytest.approx(
        100 * by_bytes / 0.5
    )
    few = dict(traced, snap_t1=dict(w1, **{
        'pygrid_serving_expert_bytes_total{kind="read",path="step"}': 11.0 * expert,
        'pygrid_serving_expert_bytes_total{kind="read",path="prefill"}': 21.0 * expert,
    }))
    assert spec.load_reader("expert_ffn_roofline_pct.longdoc")(few) == pytest.approx(
        100 * by_ops / 0.5
    )
    # the parent of the PR that added the family has none of the counters,
    # a training cell no engine: nothing to read, and nothing raised
    gone = ("expert_rows", "expert_bytes", "state_bytes", "prefill_tokens")
    bare = {k: v for k, v in w1.items() if not any(g in k for g in gone)}
    parent = dict(traced, snap_w0=bare, snap_w1=bare, snap_t0=bare, snap_t1=bare,
                  trace={"ops": {"fusion_f32_64": [3.0, 10]}})
    for name in (m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".longdoc")):
        assert spec.load_reader(name)(parent) is None, name
        assert spec.load_reader(name)({"kind": "train"}) is None, name
