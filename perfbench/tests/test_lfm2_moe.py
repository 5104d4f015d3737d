"""The lfm2_moe adapter (``models/lfm2_moe.py``) and its cell,
``agent-saturate``: the contract's eleven names, pinned digests of the
seeded weights, the counts against a hand count at the published size,
every catalog key in the file, the router (the bias moves the choice and
not the weights, at the published widths for a fifth to two fifths of the
tokens), the short convolution's two taps alone carrying a token two
positions and no further, the cell rehearsed tiny on the CPU with its ways
of going wrong (the int8 control, a served token moved by one, a router
that ignores its bias), and the five readers on hand-written scrapes."""

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

FIXTURE = spec.BENCH_DIR / "tests/fixtures/lfm2_moe.weights.sha256.json"
CELL = "agent-saturate"
CONFIG = spec.BENCH_DIR / "configs/lfm2-24b-a2b.json"
KEPT = ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv"]


def _digest(leaf) -> str:
    a = np.asarray(leaf)
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()


def _digests(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): _digest(leaf) for path, leaf in flat}


@pytest.fixture(scope="module")
def model():
    return spec.load_model("lfm2_moe")


@pytest.fixture(scope="module")
def cfg():
    return tiny_cell(CELL)["config"]


def test_the_adapter_defines_the_contract_s_eleven_names(model):
    assert len(spec.MODEL_CONTRACT) == 11
    assert all(callable(getattr(model, name)) for name in spec.MODEL_CONTRACT)
    assert spec.check_rules(BENCH) == []


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_seeded_weights_are_pinned_and_the_two_forms_hold_the_same_values(model, cfg, seed):
    pinned = json.loads(FIXTURE.read_text())["seeds"][str(seed)]
    for dtype in ("bfloat16", "float32"):
        w = model.make_weights(seed, cfg, dtype)
        assert _digests(w) == pinned[f"make_weights.{dtype}"]
        assert {x.dtype.name for x in jax.tree.leaves(w)} == {dtype}
    params = model.make_program_params(seed, cfg, "bfloat16")
    assert _digests(model.to_reference(params, cfg)) == pinned["make_weights.bfloat16"]
    assert "head" not in params  # tied to the embedding
    dense, attn, conv = params["layers"][0], params["layers"][1], params["layers"][2]
    # the dense layer: a conv mixer, an MLP of 96, no router
    assert dense["w_in"].shape == (64, 192) and dense["conv_w"].shape == (3, 64)
    assert dense["w_gate"].shape == (64, 96) and "router" not in dense
    assert attn["wq"].shape == (64, 64) and attn["wk"].shape == (64, 32)
    assert attn["q_norm"].shape == (16,) and "conv_w" not in attn
    for lp in (attn, conv):
        assert lp["router"].shape == (64, 8) and lp["expert_bias"].shape == (8,)
        assert lp["w_gate"].shape == (8, 64, 32) and lp["w_down"].shape == (8, 32, 64)
        # the router's columns in pairs w, -w
        router = np.asarray(lp["router"], np.float32)
        assert (router[:, 0::2] == -router[:, 1::2]).all()
    ref = model.to_reference(params, cfg)["layers"][2]
    assert ref["conv"].shape == (64, 3)
    assert (np.asarray(ref["conv"], np.float32).T == np.asarray(conv["conv_w"], np.float32)).all()


def test_counts_match_a_hand_count_at_the_published_size(model):
    cfg = json.loads(CONFIG.read_text())
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert (conv, attn) == (16_783_360, 10_485_888)
    mlp = 3 * 2048 * 11_776
    expert = model.expert_params(cfg)
    assert expert == 3 * 2048 * 1536 == 9_437_184 and mlp == 72_351_744
    routed = 64 * expert + 2048 * 64 + 64
    assert routed == 604_110_912
    assert model.layer_params(cfg, 0) == conv + 4096 + mlp == model.dense_layer_params(cfg, 0)
    assert model.layer_params(cfg, 1) == attn + 4096 + routed
    assert model.layer_params(cfg, 2) == conv + 4096 + routed
    assert model.dense_layer_params(cfg, 2) == conv + 4096 + 2048 * 64 + 64
    total = (conv + 4096 + mlp) + 2 * attn + 6 * conv + 8 * (routed + 4096) + 65_536 * 2048 + 2048
    deploy = cfg["deployment"]
    assert model.param_count(cfg) == total == deploy["parameters"] == 5_177_950_976
    assert model.weights_bytes(cfg) == 2 * total == deploy["weights_bytes"]
    # all 40 layers as published (two dense): the model's own name
    whole = dict(cfg, **deploy["published"])
    assert model.param_count(whole) == 23_843_661_440
    a_token = model.param_count(whole) - 38 * (64 - 4) * expert
    assert round(a_token / 1e7) == 233  # 2.33B a token
    assert (model.n_attention(cfg), model.n_conv(cfg), model.n_expert_layers(cfg)) == (2, 7, 8)
    assert model.kv_bytes_per_token(cfg) == deploy["kv_bytes_per_token"] == 4096
    state = 7 * 2 * 2048 * 2
    assert model.state_bytes_per_slot(cfg) == state == deploy["state_bytes_per_slot"] == 57_344
    block = 64 * 4096
    assert deploy["env"]["PYGRID_KV_BUDGET"] == 64 * state + (64 * 72 + 1) * block
    assert deploy["served_context"] == 72 * 64 == 4096 + 512
    assert model.expert_ffn_bytes(cfg, 3) == 3 * 2 * expert == 3 * 18_874_368
    assert model.expert_ffn_flops(cfg, 100) == 200 * expert
    # a position: 2 a matmul parameter, the taps and gates of seven convs,
    # the scores and values of two attention layers, the head where asked
    mats = 7 * (conv - 3 * 2048) + 2 * (attn - 128) + mlp + 8 * (2048 * 64 + 4 * expert)
    want = 2 * mats + 7 * 8 * 2048 + 2 * 4 * 2048 * 300
    assert model.forward_flops_per_position(cfg, 300, head=False) == want
    assert model.forward_flops_per_position(cfg, 300) == want + 2 * 2048 * 65_536
    assert 1.02e9 < model.forward_flops_per_position(cfg, 0, head=False) < 1.04e9
    dense = 2 * (total - 8 * 64 * expert)
    assert model.dense_step_bytes(cfg) == dense
    assert 0.92 < 8 * 64 * 2 * expert / (2 * total) < 0.94  # a step is expert weights
    assert model.decode_step_bytes(cfg, 64, 1000, 128) == (
        dense + 128 * 2 * expert + 2 * 64 * state + 1000 * 4096
    )
    with pytest.raises(NotImplementedError, match="no cell trains"):
        model.train_loss({}, False)
    with pytest.raises(ValueError, match="use_expert_bias"):
        model.make_weights(0, dict(cfg, use_expert_bias=False), "bfloat16")
    with pytest.raises(ValueError, match="layer_types"):
        model.make_weights(0, dict(cfg, num_hidden_layers=8), "bfloat16")


def test_every_catalog_key_is_in_the_file_and_three_are_cut():
    cfg = json.loads(CONFIG.read_text())
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 4, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    }
    assert {k: cfg[k] for k in published} == published
    all_forty = [
        "full_attention" if i % 4 == 2 else "conv" for i in range(40)
    ]
    cut = {
        "num_hidden_layers": (40, 9), "num_dense_layers": (2, 1),
        "layer_types": (all_forty, KEPT),
    }
    assert cfg["reduced"] == list(cut) and set(cfg["reduced_why"]) == set(cut)
    for key, (was, now) in cut.items():
        assert cfg[key] == now and cfg["deployment"]["published"][key] == was
    assert all_forty[1:10] == KEPT  # published layers 1-9
    assert set(cfg["assumed"]) >= {"head_dim", "tie_word_embeddings", "topk_eps", "expert_bias"}
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and "9 layers of 40: host share over deployed" in cell["why"]
    tr = json.loads((spec.BENCH_DIR / "traffic/agent-saturate.json").read_text())
    assert (tr["loop"], tr["callers"], tr["grid"], tr["cycles"]) == ("closed", 96, 128, 24)
    assert tr["prompt_len"] == {"dist": "log_uniform", "lo": 256, "hi": 4096}
    assert tr["n_new"] == {"dist": "log_uniform", "lo": 64, "hi": 512}
    assert tr["doors"] == {"ws": 3, "http": 1}
    assert (tr["lead_in_s"], tr["drain_s"], tr["trace_s"], tr["check"]["sample"]) == (10, 60, 6, 48)
    assert cfg["deployment"]["env"]["PYGRID_SERVING_SLOTS"] == 64


def test_the_bias_moves_the_choice_and_not_the_weights(model):
    """At the published widths, on normed hidden states of unit scale: the
    drawn bias changes the chosen four for a fifth to two fifths of the
    tokens; the weights of a choice are the unbiased scores over their sum
    + 1e-6, times the scale."""
    cfg = json.loads(CONFIG.read_text())
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2000, 2048), jnp.float32)
    lp = model._layer(
        jax.random.fold_in(key, 1), model._items(cfg), False, False, "float32"
    )
    router, bias = lp["router"], lp["expert_bias"]
    assert router.shape == (2048, 64) and bias.shape == (64,)
    assert 0.008 < float(jnp.std(bias)) < 0.017  # EXPERT_BIAS_SCALE 0.0125
    with jax.default_matmul_precision("highest"):
        idx, p = model.route(x, router, bias, cfg)
        blind, _ = model.route(x, router, None, cfg)
        s = np.asarray(jax.nn.sigmoid(x @ router))
    moved = (np.sort(np.asarray(idx), 1) != np.sort(np.asarray(blind), 1)).any(1).mean()
    assert 0.2 <= moved <= 0.4, moved
    chosen = np.take_along_axis(s, np.asarray(idx), 1)
    np.testing.assert_allclose(
        np.asarray(p), chosen / (chosen.sum(1, keepdims=True) + 1e-6), rtol=1e-6
    )
    _, doubled = model.route(x, router, bias, dict(cfg, routed_scaling_factor=2))
    np.testing.assert_allclose(np.asarray(doubled), 2 * np.asarray(p), rtol=1e-6)
    # every expert still gets rows: the bias tilts the load, it starves nobody
    load = np.bincount(np.asarray(idx).ravel(), minlength=64)
    assert load.min() > 0.3 * load.mean() and load.max() < 2.5 * load.mean()


def test_a_conv_layer_carries_a_token_two_positions_and_no_further(model, cfg):
    """With the attention layer's output projection zeroed only the taps
    carry anything between positions: four conv layers of three taps reach
    back eight positions and not nine."""
    w = model.make_weights(1, cfg, "float32")
    w = dict(w, layers=[
        dict(lw, wo=jnp.zeros_like(lw["wo"])) if "wo" in lw else lw for lw in w["layers"]
    ])
    seq = np.random.default_rng(1).integers(0, 128, (1, 16))
    other = seq.copy()
    other[0, 0] = (other[0, 0] + 1) % 128
    a = np.asarray(model.logits(w, jnp.asarray(seq), cfg))[0]
    b = np.asarray(model.logits(w, jnp.asarray(other), cfg))[0]
    assert np.abs(a[8] - b[8]).max() > 1e-6
    assert np.abs(a[9:] - b[9:]).max() == 0.0
    assert np.abs(a[0] - b[0]).max() > 0.1  # and of course at once


def test_an_expert_takes_as_many_rounds_as_its_positions_need(model, cfg):
    w = model.make_weights(2, cfg, "float32")
    seq = jnp.asarray(np.random.default_rng(2).integers(0, 128, (1, 64)))
    real = model.ROUND
    try:
        whole = np.asarray(model.logits(w, seq, cfg))
        model.ROUND = 8
        assert np.abs(np.asarray(model.logits(w, seq, cfg)) - whole).max() <= 1e-5
    finally:
        model.ROUND = real


def test_the_cell_rehearses_and_its_layers_read():
    """``test_rehearsal.py``'s walk for this cell, and what it reads."""
    said = []
    cell, run = rehearse(CELL, seed=2**31 + 77, log=said.append)
    assert run["verdict"]["correct"] is True, run["verdict"]
    assert run["compiles_in_window"] == 0 and run["failed"] == 0 and run["attempted"] > 0
    assert {r["door"] for r in run["ctx"]["results"]} == {"ws", "http"}
    line = runner.result_line(BENCH, cell, run, CPU, trace=False)
    assert set(line["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    ctx = dict(run["ctx"], device_kind="TPU v5 lite")
    layer = spec.read_per_layer(BENCH, CELL, ctx)
    named = {m["name"]: m for m in spec.metrics_for(BENCH, "per_layer", CELL)}
    unread = set(named) - set(layer)
    assert unread == {"expert_ffn_roofline_pct.agent", "device_idle_pct.sat", "hbm_peak_gb.sat"}
    published = ("decode_hbm_floor_pct.agent", "step_mfu_pct.agent")
    for key, m in layer.items():
        assert math.isfinite(m["value"]) and m["value"] >= 0.0
        if "_pct" in key and key not in published:
            assert m["value"] <= 100.0, key
    assert layer["kv_read_share_pct.sat"]["value"] == 100.0  # the gather
    assert layer["dispatch_ahead_pct.sat"]["value"] > 50.0
    assert layer["prefill_padding_pct.agent"]["value"] > 0.0
    assert layer["batch_occupancy_mean"]["value"] > 3.0  # of 4 slots


def test_the_control_a_moved_token_and_a_blind_router_come_out_not_correct(monkeypatch):
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

    # a program whose router ignores expert_bias: another four for some
    # tokens, and the comparison says so
    from pygrid_tpu.models import moe

    route = moe.route_topk
    monkeypatch.setattr(
        moe, "route_topk",
        lambda x, w, k, sigmoid=False, bias=None: route(x, w, k, sigmoid, None),
    )
    cell, blind = rehearse(CELL, seed=7)
    assert blind["verdict"]["correct"] is False


# ── the readers, on hand-written scrapes ─────────────────────────────────


def _snap(true, padded, prefill_s, prefills, step_bytes, prefill_bytes, routed,
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
        p + 'expert_rows_total{kind="held"}': float(routed),
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
    expert = 2 * 9_437_184
    state = 57_344
    w0 = _snap(1000, 2000, 1.0, 2, 10 * expert, 20 * expert, 8000, 5 * state, 100, 20, 0.5, 1000)
    # the window: 10 prompts of 1,500 true tokens in 2,048-buckets, 100
    # steps of 64 live rows; 8 expert layers, 4 a token
    positions = 20_480 + 6400
    w1 = _snap(
        1000 + 15_000, 2000 + 20_480, 1.0 + 1.0, 2 + 10, (10 + 50_000) * expert,
        (20 + 5000) * expert, 8000 + 4 * 8 * positions, (5 + 6400) * state,
        100 + 150_000, 20 + 100, 0.5 + 2.0, 1000 + 6400,
    )
    ctx = {
        "kind": "serve", "seconds": 51.0, "snap_w0": w0, "snap_w1": w1, "engine": w1["engine"],
        "results": [{"ok": True, "prompt_len": 1500, "n_new": 200}, {"ok": False}],
        "trace": None, "snap_t0": None, "snap_t1": None,
    }
    read = lambda name: spec.load_reader(name)(ctx)  # noqa: E731
    assert read("prefill_padding_pct.agent") == pytest.approx(100 * (1 - 15_000 / 20_480))
    assert read("prefill_ms_per_ktok.agent") == pytest.approx(1000.0 / 15.0)
    moved = (
        100 * model.dense_step_bytes(cfg) + 50_000 * expert + 2 * 6400 * state
        + 150_000 * 262_144
    )
    assert read("decode_hbm_floor_pct.agent") == pytest.approx(100 * moved / 819e9 / 2.0)
    flops = model.forward_flops_per_position
    ops = (
        15_000 * flops(cfg, 750.0, head=False) + 10 * 2 * 2048 * 65_536
        + 6400 * flops(cfg, 1600.0)
    )
    assert read("step_mfu_pct.agent") == pytest.approx(100 * ops / 197e12 / 3.0)
    assert read("expert_ffn_roofline_pct.agent") is None  # untraced
    traced = dict(ctx, snap_t0=w0, snap_t1=w1, trace={"ops": {
        "grouped_expert_ffn": [1.5, 440], "grouped_expert_ffn_f32_2048_4096": [0.5, 5],
        "fusion_f32_64": [3.0, 10],
    }})
    # the larger of the bytes' floor and the operations' floor
    by_bytes = (50_000 + 5000) * expert / 819e9
    by_ops = model.expert_ffn_flops(cfg, 4 * 8 * positions) / 197e12
    assert by_bytes > by_ops
    reader = spec.load_reader("expert_ffn_roofline_pct.agent")
    assert reader(traced) == pytest.approx(100 * by_bytes / 2.0)
    few = dict(traced, snap_t1=dict(w1, **{
        'pygrid_serving_expert_bytes_total{kind="read",path="step"}': 11.0 * expert,
        'pygrid_serving_expert_bytes_total{kind="read",path="prefill"}': 21.0 * expert,
    }))
    assert reader(few) == pytest.approx(100 * by_ops / 2.0)
    # the parent of the PR that added the family has none of the counters,
    # a training cell no engine: nothing to read, and nothing raised
    gone = ("expert_rows", "expert_bytes", "state_bytes", "prefill_tokens")
    bare = {k: v for k, v in w1.items() if not any(g in k for g in gone)}
    parent = dict(traced, snap_w0=bare, snap_w1=bare, snap_t0=bare, snap_t1=bare,
                  trace={"ops": {"fusion_f32_64": [3.0, 10]}})
    names = [m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".agent")]
    assert len(names) == 5
    for name in names:
        assert spec.load_reader(name)(parent) is None, name
        assert spec.load_reader(name)({"kind": "train"}) is None, name
