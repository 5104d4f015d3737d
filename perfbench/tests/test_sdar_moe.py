"""The sdar_moe adapter (``models/sdar_moe.py``) and its cell,
``blockdiff-saturate``: pinned digests of the seeded weights, the counts
against a hand count, the replay against the plain generate, the cell
rehearsed tiny on the CPU with its ways of going wrong (the int8 control, a
served token altered, an answer whose ``reveal_step`` breaks the schedule),
and the five readers on hand-written scrapes."""

import hashlib
import json
import math

import jax
import numpy as np
import pytest

import run as runner
from lib import serving, spec
from test_rehearsal import BENCH, CPU, rehearse
from tiny import tiny_cell

FIXTURE = spec.BENCH_DIR / "tests/fixtures/sdar_moe.weights.sha256.json"
CELL = "blockdiff-saturate"


def _digest(leaf) -> str:
    a = np.asarray(leaf)
    return hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()


def _digests(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): _digest(leaf) for path, leaf in flat}


@pytest.fixture(scope="module")
def model():
    return spec.load_model("sdar_moe")


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
    assert layer["w_gate"].shape == (8, 64, 32) and layer["w_down"].shape == (8, 32, 64)
    assert params["head"].shape == (64, 128) and params["embed"].shape == (128, 64)


def test_counts_match_a_hand_count_at_the_published_size(model):
    cfg = json.loads((spec.BENCH_DIR / "configs/sdar-30b-a3b-chat.json").read_text())
    # q 2048 x 4096, k and v 2048 x 512 each, o 4096 x 2048, two head norms
    # of 128, two norms of 2048, the router 2048 x 128
    dense = 8_388_608 + 2 * 1_048_576 + 8_388_608 + 256 + 4_096 + 262_144
    assert model.dense_layer_params(cfg) == dense == 18_878_720 + 262_144
    assert model.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    layer = dense + 128 * 4_718_592
    assert layer == 623_120_640
    total = 6 * layer + 2 * 151_936 * 2048 + 2048
    assert model.param_count(cfg) == total == cfg["deployment"]["parameters"] == 4_361_055_744
    assert 2 * total == cfg["deployment"]["weights_bytes"]
    assert model.kv_bytes_per_token(cfg) == cfg["deployment"]["kv_bytes_per_token"] == 12_288
    env = cfg["deployment"]["env"]
    assert env["PYGRID_KV_BUDGET"] == 1024 * 64 * 12_288
    # what a forward reads whatever the routing, and a touched expert's bytes
    assert model.dense_forward_bytes(cfg) == 2 * (6 * dense + 151_936 * 2048 + 2048)
    assert model.expert_ffn_bytes(cfg, 6 * 128) == 6 * 128 * 9_437_184
    # a position: 2 a matmul parameter (attention, router, 8 experts) a layer,
    # the head once, 4 x 32 x 128 an attended position a layer
    per_layer = 2 * (2048 * (4096 + 1024) + 4096 * 2048 + 2048 * 128 + 8 * 4_718_592)
    want = 6 * (per_layer + 4 * 4096 * 300) + 2 * 2048 * 151_936
    assert model.forward_flops_per_position(cfg, 300) == want
    assert model.expert_ffn_flops(cfg, 256) == 2 * 256 * 8 * 4_718_592
    # every published width as the source has it; depth alone is cut
    src = {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "num_experts": 128, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "vocab_size": 151_936, "intermediate_size": 6144,
        "max_position_embeddings": 32_768, "rope_theta": 1_000_000,
    }
    assert {k: cfg[k] for k in src} == src
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 6
    entry = next(c for c in BENCH["configs"] if c["name"] == "sdar-30b-a3b-chat")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_mix_yields_what_the_cell_says(model):
    from lib import traffic as T

    tr = json.loads((spec.BENCH_DIR / "traffic/blockdiff-saturate.json").read_text())
    built = T.build(tr, 2**31 + 9, 51.0)
    first = built["requests"][:128]
    steps = [r["fields"]["denoising_steps"] for r in first]
    assert sorted(set(steps)) == [1, 2, 4]
    assert [steps.count(s) for s in (4, 2, 1)] == [64, 32, 32]
    assert {r["n_new"] for r in first} == {256}
    # a whole block of four costs its denoising forwards and one commit
    forwards = sum(64 * (s + 1) for s in steps)
    assert 128 * 256 / forwards == pytest.approx(1024 / 960)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_the_replay_finds_the_plain_generate_s_states(model, cfg, steps):
    """What the plain loop made is, replayed from its answer alone, the
    reference's first choice at every served position: gaps of nought; a
    token changed after the fact is below the best; an answer that names
    other forwards than the schedule's is malformed."""
    w = model.make_weights(4, cfg, "float32")
    prompt = np.random.default_rng(steps).integers(0, 128, (1, 13)).astype(np.int32)
    answer = {k: np.asarray(v) for k, v in model.generate(w, cfg, prompt, 9, steps).items()}
    request = {"prompt": prompt, "n_new": 9, "fields": {"denoising_steps": steps}}
    gaps, cgaps = model.served_gaps(w, cfg, request, answer, 128, model.control_weights(w))
    assert gaps.shape == cgaps.shape == (9,) and np.abs(gaps).max() <= 1e-5
    assert (cgaps >= -1e-5).all()
    other = dict(answer, tokens=(answer["tokens"] + 1) % 128)
    assert model.served_gaps(w, cfg, request, other, 128)[0].max() > 1e-3
    late = dict(answer, reveal_step=answer["reveal_step"] + 1)
    with pytest.raises(ValueError, match="the schedule says"):
        model.served_gaps(w, cfg, request, late, 128)
    with pytest.raises(KeyError):
        model.served_gaps(w, cfg, request, {"tokens": answer["tokens"]}, 128)
    short = dict(answer, dropped_tokens=answer["dropped_tokens"][:, :-1])
    with pytest.raises(ValueError, match="positions"):
        model.served_gaps(w, cfg, request, short, 128)


def test_the_cell_rehearses_and_its_layers_read():
    """``test_rehearsal.py``'s walk for this cell, with one more metric that
    only a traced run on the chip can read: the kernel's roofline share."""
    said = []
    cell, run = rehearse(CELL, seed=2**31 + 77, log=said.append)
    assert run["verdict"]["correct"] is True, run["verdict"]
    assert run["compiles_in_window"] == 0 and run["failed"] == 0 and run["attempted"] > 0
    assert {c["name"]: c["value"] for c in run["verdict"]["compared"]}["malformed_answers"] == 0
    doors = {r["door"] for r in run["ctx"]["results"]}
    assert doors == {"ws", "http"}
    sent = {r["fields"]["denoising_steps"] for r in run["ctx"]["results"]}
    assert sent == {1, 2, 4}
    assert all(set(r["answer"]) == {
        "reveal_step", "dropped_tokens", "dropped_reveal_step"
    } for r in run["ctx"]["results"] if r["ok"])
    line = runner.result_line(BENCH, cell, run, CPU, trace=False)
    assert set(line["metrics"]) == {"gen_tokens_per_s", "setup_s"}
    ctx = dict(run["ctx"], device_kind="TPU v5 lite")
    layer = spec.read_per_layer(BENCH, CELL, ctx)
    named = {m["name"] for m in spec.metrics_for(BENCH, "per_layer", CELL)}
    traced_only = ("device_idle_pct", "hbm_peak_gb", "expert_ffn_roofline_pct")
    assert all(m.startswith(traced_only) for m in named - set(layer)), named - set(layer)
    for key, m in layer.items():
        assert math.isfinite(m["value"])
        if "_pct" in key:
            assert 0.0 <= m["value"] <= 100.0
    assert 0.8 <= layer["tokens_per_row_forward.blockdiff"]["value"] <= 1.4
    assert 15.0 <= layer["commit_forward_pct.blockdiff"]["value"] <= 35.0
    assert layer["kv_read_share_pct.sat"]["value"] == 100.0


def test_the_control_and_an_altered_token_come_out_not_correct():
    cell, run = rehearse(CELL, seed=7, control=True)
    check = cell["traffic"]["check"]
    assert run["verdict"]["correct"] is True
    control = run["verdict"]["control"]
    # the control has to fail one of the cell's limits, not each
    assert (
        control["served_gap_max"] > check["gap_max_limit"]
        or control["served_gap_mean"] > check["gap_mean_limit"]
    )

    def alter_tokens(engine):
        emit = engine._emit

        def emit_other(slot, row, tokens):
            return emit(slot, row, tuple((t + 1) % 128 for t in tokens))

        engine._emit = emit_other

    cell, broken = rehearse(CELL, seed=7, engine_hook=alter_tokens)
    assert broken["verdict"]["correct"] is False
    over = {c["name"] for c in broken["verdict"]["compared"] if c["value"] > c["limit"]}
    assert "served_gap_max" in over
    assert runner.result_line(BENCH, cell, broken, CPU, False)["correct"] is False


def test_an_answer_that_breaks_the_schedule_is_malformed(model):
    cell = tiny_cell(CELL)
    cfg, w = cell["config"], None
    w = model.make_weights(11, cfg, "float32")
    results = []
    for i, steps in enumerate((4, 2)):
        request = {"i": i, "prompt_len": 9, "n_new": 6, "prefix": None}
        from lib import traffic as T

        prompt = T.prompt_tokens(cell["traffic"], 11, request, 128)
        answer = model.generate(w, cfg, prompt, 6, steps)
        if i:
            answer["reveal_step"] = [[0] * 6]  # steps 2 reveals two a forward, not all
        results.append({
            **request, "ok": True, "counted": True, "tokens": answer.pop("tokens"),
            "answer": answer, "fields": {"denoising_steps": steps},
        })
    verdict = serving.check_served(cell, model, 11, results, False)
    assert verdict["correct"] is False and len(verdict["malformed"]) == 1
    assert "the schedule says" in verdict["malformed"][0]


# ── the readers, on hand-written scrapes ─────────────────────────────────


def _snap(tokens, denoise, commit, step_bytes, prefill_bytes, pages, steps, step_s, live):
    p = "pygrid_serving_"
    return {
        p + 'tokens_total{model="bench"}': float(tokens),
        p + 'block_forwards_total{kind="denoise"}': float(denoise),
        p + 'block_forwards_total{kind="commit"}': float(commit),
        p + 'expert_bytes_total{kind="read",path="step"}': float(step_bytes),
        p + 'expert_bytes_total{kind="read",path="prefill"}': float(prefill_bytes),
        p + 'kv_pages_total{kind="read"}': float(pages),
        p + 'kv_pages_total{kind="table"}': float(pages),
        p + 'dispatch_seconds_sum{path="step",width="64"}': float(step_s),
        p + 'dispatch_seconds_count{path="step",width="64"}': float(steps),
        p + 'dispatch_rowsteps_total{kind="live"}': float(live),
        p + 'dispatch_rowsteps_total{kind="computed"}': float(64 * steps),
        "engine": {"device_kind": "TPU v5 lite", "kv_block_bytes": 786_432},
    }


def test_readers_on_hand_written_scrapes(model):
    cfg = json.loads((spec.BENCH_DIR / "configs/sdar-30b-a3b-chat.json").read_text())
    expert = 9_437_184
    w0 = _snap(1000, 700, 250, 100 * expert, 10 * expert, 5000, 20, 0.5, 1200)
    w1 = _snap(1000 + 1024, 700 + 704, 250 + 256, (100 + 7680) * expert, (10 + 640) * expert,
               5000 + 10_240, 20 + 10, 0.5 + 0.25, 1200 + 640)
    ctx = {
        "kind": "serve", "seconds": 51.0, "snap_w0": w0, "snap_w1": w1, "engine": w1["engine"],
        "results": [{"ok": True, "prompt_len": 200, "n_new": 256}, {"ok": False}],
        "trace": None, "snap_t0": None, "snap_t1": None,
    }
    read = lambda name: spec.load_reader(name)(ctx)  # noqa: E731
    assert read("tokens_per_row_forward.blockdiff") == pytest.approx(1024 / 960)
    assert read("commit_forward_pct.blockdiff") == pytest.approx(100 * 256 / 960)
    moved = 10 * model.dense_forward_bytes(cfg) + 7680 * expert + 10_240 * 786_432
    assert read("step_hbm_floor_pct.blockdiff") == pytest.approx(100 * moved / 819e9 / 0.25)
    flops = 4 * 640 * model.forward_flops_per_position(cfg, 328.0)
    assert read("step_mfu_pct.blockdiff") == pytest.approx(100 * flops / 197e12 / 0.25)
    assert read("expert_ffn_roofline_pct.blockdiff") is None  # untraced
    # a traced stretch: the counter's growth between its two ends, prefills
    # and steps alike, over the kernel's seconds whatever its shapes
    traced = dict(ctx, snap_t0=w0, snap_t1=w1, trace={"ops": {
        "grouped_expert_ffn_f32_6144_2048": [0.08, 60], "grouped_expert_ffn_f32_8192_2048": [0.02, 5],
        "fusion_f32_64": [3.0, 10],
    }})
    assert spec.load_reader("expert_ffn_roofline_pct.blockdiff")(traced) == pytest.approx(
        100 * (7680 + 640) * expert / 819e9 / 0.10
    )
    # the parent of the PR that added the family has none of the counters,
    # a training cell no engine: nothing to read, and nothing raised
    bare = {k: v for k, v in w1.items() if "block_forwards" not in k and "expert_bytes" not in k}
    parent = dict(traced, snap_w0=bare, snap_w1=bare, snap_t0=bare, snap_t1=bare)
    for name in (m["name"] for m in BENCH["per_layer"] if m["name"].endswith(".blockdiff")):
        assert spec.load_reader(name)(parent) is None, name
        assert spec.load_reader(name)({"kind": "train"}) is None, name
