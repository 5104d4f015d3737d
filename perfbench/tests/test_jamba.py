"""The jamba adapter (``models/jamba.py``) and its cell, ``reason-saturate``:
pinned digests of the seeded weights, the counts against a hand count, a
state that lives for hundreds of tokens, and the cell rehearsed tiny on the
CPU with its two ways of going wrong: the int8 control, and an engine whose
recurrent state is zeroed between dispatches."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as runner
from lib import spec
from test_rehearsal import BENCH, CPU, rehearse
from tiny import tiny_cell

FIXTURE = spec.BENCH_DIR / "tests/fixtures/jamba.weights.sha256.json"
CELL = "reason-saturate"


def _digest(leaf) -> str:
    a = np.asarray(leaf)
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()


def _digests(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): _digest(leaf) for path, leaf in flat}


@pytest.fixture(scope="module")
def model():
    return spec.load_model("jamba")


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
    assert _digests(params) == pinned["make_program_params.bfloat16"]
    back = model.to_reference(params, cfg)
    assert _digests(back) == pinned["make_weights.bfloat16"]
    # the published layout: A_log [d_inner, n], conv_w [d_inner, K]
    mamba = back["layers"][0]
    assert mamba["A_log"].shape == (128, 16) and mamba["conv_w"].shape == (128, 4)
    np.testing.assert_allclose(
        np.exp(np.asarray(mamba["A_log"], np.float32)[0]), np.arange(1, 17), rtol=1e-2
    )
    dt = np.log1p(np.exp(np.asarray(model.make_weights(seed, cfg, "float32")["layers"][0]["dt_bias"])))
    assert 0.001 <= dt.min() and dt.max() <= 0.1


def test_counts_match_a_hand_count_at_the_published_size(model):
    cfg = json.loads((spec.BENCH_DIR / "configs/ai21-jamba2-3b.json").read_text())
    # Mamba layer: in_proj 2560 x 10240, conv 5120 x 4 + 5120, x_proj 5120 x 192,
    # dt_proj 160 x 5120 + 5120, A_log 5120 x 16, D 5120, out_proj 5120 x 2560,
    # inner norms 160 + 16 + 16; the gated MLP 3 x 2560 x 8192; two norms
    mixer = 26_214_400 + 25_600 + 983_040 + 824_320 + 81_920 + 5_120 + 13_107_200 + 192
    assert mixer == 41_241_792
    assert model.layer_params(cfg, 0) == mixer + 62_914_560 + 5_120 == 104_161_472
    attn = 2 * 6_553_600 + 2 * 327_680
    assert model.layer_params(cfg, 21) == attn + 62_914_560 + 5_120 == 76_682_240
    total = 26 * 104_161_472 + 2 * 76_682_240 + 65_536 * 2_560 + 2_560
    assert model.param_count(cfg) == total == 3_029_337_472
    assert 2 * total == cfg["deployment"]["weights_bytes"] == 6_058_674_944
    assert model.kv_bytes_per_token(cfg) == cfg["deployment"]["kv_bytes_per_token"]
    assert model.state_bytes_per_slot(cfg) == cfg["deployment"]["state_bytes_per_slot"]
    # forward and backward a token at 2,048: 6 a matmul parameter, attention
    # in 2 layers, the recurrence's 27 an element of state in 26
    matmul = model.matmul_params(cfg)
    assert matmul == total - 26 * (25_600 + 5_120 + 81_920 + 5_120 + 192 + 5_120) - 2 * 5_120 - 2_560
    assert model.train_flops_per_token(cfg, 2048) == (
        6.0 * matmul + 2 * 12.0 * 2560 * 1024.5 + 26 * 27.0 * 5120 * 16
    )
    with pytest.raises(NotImplementedError, match="no cell trains"):
        model.train_loss({}, False)
    with pytest.raises(ValueError, match="num_experts"):
        model.make_weights(0, dict(cfg, num_experts=16), "bfloat16")


def test_every_published_key_is_in_the_file_unchanged():
    cfg = json.loads((spec.BENCH_DIR / "configs/ai21-jamba2-3b.json").read_text())
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
        "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
        "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1,
        "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True, "vocab_size": 65536,
    }
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == []
    entry = next(c for c in BENCH["configs"] if c["name"] == "ai21-jamba2-3b")
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


def test_a_token_256_positions_back_moves_the_logits_through_the_mamba_layers_alone(model, cfg):
    """With the attention layers' output projection zeroed only the
    recurrence carries position 0 to position 256: a lost state shows."""
    w = model.make_weights(1, cfg, "float32")
    w = dict(w, layers=[
        dict(lw, wo=jnp.zeros_like(lw["wo"])) if "wo" in lw else lw for lw in w["layers"]
    ])
    seq = np.random.default_rng(1).integers(0, 128, (1, 257))
    other = seq.copy()
    other[0, 0] = (other[0, 0] + 1) % 128
    a = np.asarray(model.logits(w, jnp.asarray(seq), cfg))[0]
    b = np.asarray(model.logits(w, jnp.asarray(other), cfg))[0]
    assert np.abs(a[256] - b[256]).max() > 1e-3
    assert np.abs(a[0] - b[0]).max() > 0.1  # and of course at once


def test_the_control_and_a_zeroed_state_come_out_not_correct():
    cell, run = rehearse(CELL, seed=7, control=True)
    check = cell["traffic"]["check"]
    assert run["verdict"]["correct"] is True
    assert run["verdict"]["control"]["served_gap_max"] > check["gap_max_limit"]

    def zero_state(engine):
        """The recurrent state lost between dispatches: zeroed whenever a
        token is taken off the device."""
        emit = engine._emit

        def emit_and_drop(slot, row, tok):
            engine._state = [jnp.zeros_like(a) for a in engine._state]
            return emit(slot, row, tok)

        engine._emit = emit_and_drop

    cell, broken = rehearse(CELL, seed=7, engine_hook=zero_state)
    assert broken["verdict"]["correct"] is False
    over = {c["name"] for c in broken["verdict"]["compared"] if c["value"] > c["limit"]}
    assert "served_gap_max" in over
    assert runner.result_line(BENCH, cell, broken, CPU, False)["correct"] is False


def test_the_new_readers_on_hand_written_scrapes():
    w0 = {
        'pygrid_serving_prefill_tokens_total{kind="true"}': 1000.0,
        'pygrid_serving_prefill_tokens_total{kind="padded"}': 1500.0,
        'pygrid_serving_prefill_seconds_sum': 2.0,
        'pygrid_serving_prefill_seconds_count': 10.0,
        'pygrid_serving_state_bytes_total{kind="read"}': 1e9,
        'pygrid_serving_state_bytes_total{kind="written"}': 1.1e9,
        'pygrid_serving_kv_pages_total{kind="read"}': 100.0,
        'pygrid_serving_kv_pages_total{kind="table"}': 100.0,
        'pygrid_serving_fused_steps_total{model="bench"}': 8.0,
        'pygrid_serving_dispatch_seconds_sum{path="step",width="64"}': 1.0,
        'pygrid_serving_dispatch_seconds_count{path="step",width="64"}': 100.0,
        'pygrid_serving_loop_seconds_total{phase="admit"}': 1.0,
        'pygrid_serving_loop_seconds_total{phase="fetch"}': 5.0,
    }
    w1 = {
        'pygrid_serving_prefill_tokens_total{kind="true"}': 4000.0,
        'pygrid_serving_prefill_tokens_total{kind="padded"}': 5500.0,
        'pygrid_serving_prefill_seconds_sum': 2.6,
        'pygrid_serving_prefill_seconds_count': 30.0,
        'pygrid_serving_state_bytes_total{kind="read"}': 1e9 + 200 * 64 * 1e6,
        'pygrid_serving_state_bytes_total{kind="written"}': 1.1e9 + 220 * 64 * 1e6,
        'pygrid_serving_kv_pages_total{kind="read"}': 100.0 + 200 * 64 * 32,
        'pygrid_serving_kv_pages_total{kind="table"}': 100.0 + 200 * 64 * 32,
        'pygrid_serving_fused_steps_total{model="bench"}': 24.0,
        'pygrid_serving_dispatch_seconds_sum{path="step",width="64"}': 3.3,
        'pygrid_serving_dispatch_seconds_count{path="step",width="64"}': 284.0,
        'pygrid_serving_dispatch_seconds_sum{path="fused",width="64"}': 0.2,
        'pygrid_serving_dispatch_seconds_count{path="fused",width="64"}': 2.0,
        'pygrid_serving_loop_seconds_total{phase="admit"}': 2.0,
        'pygrid_serving_loop_seconds_total{phase="build"}': 1.5,
        'pygrid_serving_loop_seconds_total{phase="emit"}': 0.5,
        'pygrid_serving_loop_seconds_total{phase="fetch"}': 25.0,
    }
    engine = {"weights_bytes": 6_000_000_000, "kv_block_bytes": 65_536,
              "device_kind": "TPU v5 lite"}
    ctx = {"kind": "serve", "seconds": 50.0, "snap_w0": w0, "snap_w1": w1, "engine": engine}
    read = {m: spec.load_reader(m) for m in (
        "host_loop_pct.reason", "decode_hbm_floor_pct.reason",
        "prefill_ms_per_ktok.reason", "prefill_padding_pct.reason",
    )}
    assert read["host_loop_pct.reason"](ctx) == pytest.approx(100 * 3.0 / 50)
    assert read["prefill_ms_per_ktok.reason"](ctx) == pytest.approx(1e3 * 0.6 / 3.0)
    assert read["prefill_padding_pct.reason"](ctx) == pytest.approx(100 * (1 - 3000 / 4000))
    # 184 per-step dispatches + 16 fused steps = 200 steps of 6 GB, 64 rows'
    # state of 1 MB read and written, 64 tables of 32 pages read
    moved = 200 * 6e9 + 2 * 200 * 64 * 1e6 + 200 * 64 * 32 * 65_536
    assert read["decode_hbm_floor_pct.reason"](ctx) == pytest.approx(
        100 * (moved / 819e9) / 2.5
    )
    # nothing to read: a training cell, and a program without the counters
    # (this PR's parent): None, not an exception
    bare = {k: v for k, v in w1.items() if "prefill_tokens" not in k
            and "state_bytes" not in k and "loop_seconds" not in k}
    old = {"kind": "serve", "seconds": 50.0, "snap_w0": bare, "snap_w1": bare,
           "engine": {"device_kind": "TPU v5 lite"}}
    for reader in read.values():
        assert reader({"kind": "train"}) is None
        assert reader(old) is None
