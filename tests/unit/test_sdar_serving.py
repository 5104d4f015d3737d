"""The block-diffusion family with routed experts (``pygrid_tpu/models/
sdar_moe.py``) through the serving engine, against the plain reference that
the benchmark keeps (``perfbench/models/sdar_moe.py``: float32, ``highest``,
every expert over every position, no cache, nothing of the program
imported).

Size (``tiny``): 2 layers, 8 experts top-2, hidden 64, 4 query heads on 2
K/V heads of 16, vocabulary 128, the mask token id 127; float32 weights on
the CPU. The program's prefill and block steps are compared with the
reference on LOGITS; the engine's answers with a plain-Python generate over
the reference, token for token and ``reveal_step`` for ``reveal_step``.

``TOL``: program and reference run the same float32 mathematics in another
order (``rsqrt`` norms, the experts grouped instead of one after another,
K/V read back through the paged pool); logits of size ~3 differ by at most
3.4e-6 over the cases below (my CPU runs, PR 34). 5e-5 is the limit the
jamba family's tests hold, fifteen times that, and thousands of times under
what a fault does (a K/V row written to the wrong page moves a logit by
0.1 and more).
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu import telemetry
from pygrid_tpu.models import decode, jamba, moe, sdar_moe
from pygrid_tpu.models import transformer as T
from pygrid_tpu.serving import EngineConfig, GenerationEngine, ProgramSet
from pygrid_tpu.utils import exceptions as E

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "perfbench") not in sys.path:
    sys.path.append(str(ROOT / "perfbench"))
from lib import spec  # noqa: E402

TOL = 5e-5
PAGE = 16
PAD = 64


@pytest.fixture(scope="module")
def model():
    return spec.load_model("sdar_moe")


@pytest.fixture(scope="module")
def cfg(model):
    cfg = json.loads((ROOT / "perfbench/configs/sdar-30b-a3b-chat.json").read_text())
    cfg.update(model.tiny(cfg))
    return cfg


@pytest.fixture(scope="module")
def scfg(model, cfg):
    return model.sdar_config(cfg)


@pytest.fixture(scope="module")
def params(model, cfg):
    return model.make_program_params(3, cfg, "float32")


@pytest.fixture(scope="module")
def weights(model, cfg):
    return model.make_weights(3, cfg, "float32")


def _blocks(named):
    return [named[a : a + 4] for a in range(0, len(named), 4)]


def _tokens(seed, n, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _engine(scfg, params, **over):
    kw = dict(max_slots=4, slot_buckets=(1, 4), min_prompt_bucket=8, block_size=PAGE)
    kw.update(over)
    return GenerationEngine(scfg, params, EngineConfig(**kw), model_id="sdar")


def _count(name, **labels):
    return sum(
        v for (n, lab), v in telemetry.counters().items()
        if n == name and labels.items() <= dict(lab).items()
    )


# ── the registry and the family's facts ──────────────────────────────────


def test_one_registry_finds_a_family_by_config_type_and_by_bundle_tag(scfg, params):
    assert decode.family_of(scfg) is sdar_moe
    assert decode.family_of(jamba.JambaConfig()) is jamba
    assert decode.family_of(T.TransformerConfig()) is decode
    with pytest.raises(ValueError, match="no served family"):
        decode.family_of(("not", "a", "config"))
    back_cfg, back = decode.from_bundle(sdar_moe.bundle(scfg, params))
    assert back_cfg == scfg
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()), back, params))
    tparams = T.init(jax.random.PRNGKey(0), T.TransformerConfig())
    assert decode.from_bundle(decode.bundle(T.TransformerConfig(), tparams))[0] == T.TransformerConfig()
    for bad in ({"family": "nobody"}, {"family": ["sdar_moe"]}, [1, 2]):
        with pytest.raises(ValueError, match="not a generative"):
            decode.from_bundle(bad)
    wrong = sdar_moe.bundle(scfg, params)
    wrong["params"]["layers"][0].pop("router")
    with pytest.raises(ValueError, match="layer 0"):
        decode.from_bundle(wrong)


def test_the_family_says_what_the_engine_asks(scfg):
    assert sdar_moe.BLOCK_LEN == 4 and decode.BLOCK_LEN == jamba.BLOCK_LEN == 1
    assert not sdar_moe.RECURRENT and sdar_moe.kv_heads(scfg) == 2
    from pygrid_tpu.serving import pagedkv

    # k and v, 2 layers, a page of 16, 2 K/V heads of 16 (NOT d_model / n_heads), float32
    assert pagedkv.block_bytes(scfg, 16, jnp.float32) == 2 * 2 * 16 * 2 * 16 * 4
    with pytest.raises(ValueError, match="block boundary"):
        sdar_moe.init_paged_cache(scfg, 2, 4, 6)


# ── the expert layer ─────────────────────────────────────────────────────


def _dense_experts(x, lp, k):
    """Every expert over every token; the router's top-k as a weight."""
    with jax.default_matmul_precision("highest"):
        r = jax.nn.softmax(x @ lp["router"], -1)
        p, idx = jax.lax.top_k(r, k)
        p = p / p.sum(-1, keepdims=True)
        hidden = jax.nn.silu(jnp.einsum("td,edf->tef", x, lp["w_gate"]))
        hidden = hidden * jnp.einsum("td,edf->tef", x, lp["w_up"])
        every = jnp.einsum("tef,efd->ted", hidden, lp["w_down"])
        return (jnp.take_along_axis(every, idx[..., None], 1) * p[..., None]).sum(1), idx


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas-interpreted", "ragged-dot"])
@pytest.mark.parametrize("tokens", [1, 16, 100])
def test_grouped_experts_equal_dense_compute_every_expert(params, kernel, tokens):
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 64))
    want, idx = _dense_experts(x, lp, 2)
    got, touched, landed = jax.jit(
        lambda x: moe.routed_experts(
            x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], 2,
            kernel=kernel, interpret=True,
        )
    )(x)
    assert float(jnp.abs(got - want).max()) <= 2e-6
    # the count the roofline reader divides by: experts that received a row
    assert int(touched) == len(np.unique(np.asarray(idx)))
    # every expert is here (``held`` not passed): every assignment lands
    assert int(landed) == tokens * 2


@pytest.mark.parametrize("kernel", [True, False], ids=["pallas-interpreted", "ragged-dot"])
def test_a_row_that_is_nobody_s_takes_no_expert_row_and_adds_nothing(params, kernel):
    """``live``: a block step's first four positions where it commits
    nothing. Their assignments take no row of any expert's group (an
    expert only they chose is not read), their result is zero, and every
    other row's is what it is without them."""
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(7), (24, 64))
    live = np.arange(24) % 3 != 1
    want, idx = _dense_experts(x, lp, 2)
    got, touched, landed = jax.jit(
        lambda x, live: moe.routed_experts(
            x, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"], 2,
            kernel=kernel, interpret=True, live=live,
        )
    )(x, jnp.asarray(live))
    got = np.asarray(got)
    assert float(np.abs(got[live] - np.asarray(want)[live]).max()) <= 2e-6
    assert not got[~live].any()
    assert int(touched) == len(np.unique(np.asarray(idx)[live]))
    assert int(landed) == 2 * int(live.sum())


def test_grouped_layout_starts_every_expert_on_a_tile_and_drops_nothing():
    ids = jnp.asarray([5, 0, 5, 5, 2, 0, 5, 5, 5], jnp.int32)
    dest, tile_expert, n_live, sizes = moe.grouped_layout(ids, 8, tile=4)
    assert sizes.tolist() == [2, 0, 1, 0, 0, 6, 0, 0]
    # expert 0: one tile, expert 2: one, expert 5: two; the rest of the
    # 9/4 + 8 tiles are dead and repeat the last live tile's expert
    assert int(n_live) == 4
    assert tile_expert.tolist()[:4] == [0, 2, 5, 5]
    assert set(tile_expert.tolist()[4:]) == {5}
    assert sorted(dest.tolist()) == [0, 1, 4, 8, 9, 10, 11, 12, 13]
    assert len(set(dest.tolist())) == 9
    for a, row in zip(ids.tolist(), dest.tolist()):
        assert tile_expert[row // 4] == a


# ── the program against the reference, on logits ─────────────────────────


def _cache_after_prefill(scfg, params, prompt, pages, bucket=32):
    cache = sdar_moe.init_paged_cache(scfg, 2, 12, PAGE)
    table = jnp.zeros((2, 128 // PAGE), jnp.int32).at[1, : len(pages)].set(
        jnp.asarray(pages, jnp.int32)
    )
    whole = len(prompt) // 4 * 4
    chunk = np.zeros(bucket, np.int32)
    chunk[:whole] = prompt[:whole]
    _, cache, read = sdar_moe.paged_prefill_chunk(
        params, cache, table, jnp.int32(1), jnp.asarray(chunk), jnp.int32(0),
        jnp.int32(whole), scfg,
    )
    return cache, table, whole, float(read)


def _rows_of(pool, pages):
    """A row's K/V in position order: ``pool`` [layers, blocks, block, G,
    dh] through its pages -> [layers, positions, G, dh]."""
    got = np.asarray(pool)[:, list(pages)]
    return got.reshape(got.shape[0], -1, *got.shape[3:])


@pytest.mark.parametrize("p_len", [8, 21, 30])
def test_prefill_and_block_steps_agree_with_the_full_forward(
    model, cfg, scfg, params, weights, p_len
):
    """The prompt's whole blocks through prefill, then every later block
    twice through the one step function: its first forward, two of its
    positions masked, which carries the block before it, whole, and
    commits it (the row's first block has none before it); then a forward
    with one position masked that carries nothing. Each forward's logits
    for the current block are the reference's for that state; ``pos``
    moves on by four only where a commit rides; a previous block that is
    nobody's writes nothing but trash block 0; and the K/V the commits
    leave are those of a prefill over the committed sequence."""
    seq = _tokens(p_len, 40)
    seq[3], seq[p_len // 4 * 4 + 1] = 127, 127  # the mask token's id, as a real token
    pages = [3, 7, 5]
    cache, table, whole, read = _cache_after_prefill(scfg, params, seq[:p_len], pages)
    assert int(cache.pos[1]) == whole
    # the last layer's experts feed nothing a prefill returns: one layer's are read
    assert 0 < read <= 8 * sdar_moe.expert_bytes(params)
    step = jax.jit(lambda cache, tok, masked, before, commit: sdar_moe.paged_decode_step(
        params, cache, table, tok, scfg, before=before, commit=commit, masked=masked,
    ))
    free = np.zeros(4, np.int32)
    worst = 0.0
    for a in range(whole, 40, 4):
        for flags, commit in (
            (np.array([False, True, False, True]), a > whole),
            (np.array([False, False, False, True]), False),
        ):
            block = np.where(flags, 0, seq[a : a + 4])  # what a masked slot says is ignored
            state = np.concatenate([seq[:a], np.where(flags, 127, seq[a : a + 4])])
            want = np.asarray(model.logits(weights, jnp.asarray(state[None]), cfg)[0, a:])
            # where no commit rides, what stands in the block's place is nobody's
            before = seq[a - 4 : a] if commit else _tokens(a, 4)
            held = np.asarray(cache.k), np.asarray(cache.v)
            got, cache, read = step(
                cache, jnp.asarray(np.stack([free, block])),
                jnp.asarray(np.stack([np.zeros(4, bool), flags])),
                jnp.asarray(np.stack([free, before])),
                jnp.asarray([False, commit]),
            )
            assert got.shape == (2, 4, 128)  # the current block's logits alone
            worst = max(worst, float(np.abs(np.asarray(got[1]) - want).max()))
            assert int(cache.pos[1]) == a and int(cache.pos[0]) == 0
            assert float(read) % sdar_moe.expert_bytes(params) == 0
            # outside trash block 0 the forward wrote the current block's
            # four rows and, where it commits, the four before them
            wrote = {(pages[i // PAGE], i % PAGE) for i in range(a - 4 * commit, a + 4)}
            for was, now in zip(held, (cache.k, cache.v)):
                changed = (np.asarray(now) != was).any(axis=(0, 3, 4))
                assert {(int(b), int(o)) for b, o in zip(*np.nonzero(changed[1:]))} <= {
                    (b - 1, o) for b, o in wrote
                }
    assert worst <= TOL, worst
    # blocks up to the last but one are committed: their K/V are what a
    # prefill of those 36 tokens writes
    clean, _, _, _ = _cache_after_prefill(scfg, params, seq[:36], pages, bucket=48)
    for got, want in ((cache.k, clean.k), (cache.v, clean.v)):
        gap = np.abs(_rows_of(got, pages)[:, :36] - _rows_of(want, pages)[:, :36])
        assert float(gap.max()) <= TOL


def test_a_shared_prefix_page_serves_a_second_prompt(model, cfg, scfg, params, weights):
    """A page ends on a block boundary, so its K/V depend on nothing after
    it: a second prompt that opens with the same 16 tokens is prefilled
    from position 16 on over the first one's page."""
    first, second = _tokens(1, 24), _tokens(2, 28)
    second[:16] = first[:16]
    cache, table, _, _ = _cache_after_prefill(scfg, params, first, [3, 7])
    table = table.at[0, :2].set(jnp.asarray([3, 9], jnp.int32))  # page 3 shared, 9 its own
    chunk = np.zeros(16, np.int32)
    chunk[:12] = second[16:28]
    _, cache, _ = sdar_moe.paged_prefill_chunk(
        params, cache, table, jnp.int32(0), jnp.asarray(chunk), jnp.int32(16),
        jnp.int32(28), scfg,
    )
    block = _tokens(3, 4)
    got, _, _ = sdar_moe.paged_decode_step(
        params, cache, table, jnp.asarray(block[None]), scfg,
        before=jnp.zeros((1, 4), jnp.int32), commit=jnp.asarray([False]),
        masked=jnp.zeros((1, 4), bool),
    )
    state = np.concatenate([second, block])
    want = np.asarray(model.logits(weights, jnp.asarray(state[None]), cfg)[0, 28:])
    assert float(np.abs(np.asarray(got[0]) - want).max()) <= TOL


def test_the_reveal_rule_takes_the_most_confident_masked_positions():
    logits = jnp.log(jnp.asarray([[
        [0.7, 0.2, 0.1], [0.1, 0.5, 0.4], [0.05, 0.05, 0.9], [0.3, 0.6, 0.1],
    ]] * 3))
    masked = jnp.asarray([[True, True, True, True], [True, True, False, True], [False] * 4])
    toks, chosen = ProgramSet._reveal(logits, masked, jnp.asarray([2, 1, 4]))
    assert toks.tolist() == [[0, 1, 2, 1]] * 3
    assert chosen.tolist() == [
        [True, False, True, False],  # 0.9 and 0.7
        [True, False, False, False],  # 0.9 is known already: 0.7
        [False] * 4,  # nothing masked, nothing revealed, whatever was asked
    ]
    # a tie goes to the earlier position
    same = jnp.zeros((1, 4, 3))
    assert ProgramSet._reveal(same, jnp.ones((1, 4), bool), jnp.asarray([1]))[1].tolist() == [
        [True, False, False, False]
    ]


# ── the engine against the plain generate ────────────────────────────────

CASES = [  # prompt length, n_new, denoising_steps
    (9, 10, 4), (16, 8, 2), (3, 5, 1), (21, 12, 4), (8, 7, 2), (30, 1, 1), (13, 16, 1),
]


@pytest.fixture(scope="module")
def served(model, cfg, scfg, params, weights):
    """Every case enqueued at once on a four-slot engine (rows in different
    phases and with different ``denoising_steps`` share dispatches), and
    the plain generate's answer beside each."""
    telemetry.reset()
    eng = _engine(scfg, params)
    try:
        prompts = [_tokens(100 + i, p) for i, (p, _, _) in enumerate(CASES)]
        prompts[0][-1] = 127  # a prompt tail that IS the mask token's id
        futures = [
            eng.enqueue(prompt[None], n, denoising_steps=steps)
            for prompt, (_, n, steps) in zip(prompts, CASES)
        ]
        got = [
            {k: np.asarray(v).tolist() for k, v in f.result(300).items()} for f in futures
        ]
        stats, ledger = eng.stats(), eng.ledger()
        # the bus is the process's: read it before another test's engine adds to it
        stats["bus"] = {
            "tokens": _count("serving_tokens_total"),
            "fused": _count("serving_fused_scans_total"),
            **{
                f"ahead_{answer}": _count("serving_dispatches_total", ahead=answer)
                for answer in ("yes", "no")
            },
            "arrivals_left": len(eng._arrivals),
            **{
                kind: _count("serving_block_forwards_total", kind=kind)
                for kind in ("denoise", "commit")
            },
            **{
                f"positions_{kind}": _count("serving_block_positions_total", kind=kind)
                for kind in ("denoise", "commit")
            },
            **{
                path: _count("serving_expert_bytes_total", kind="read", path=path)
                for path in ("step", "prefill")
            },
        }
    finally:
        eng.close()
    want = [
        model.generate(weights, cfg, prompt, n, steps, pad_to=PAD)
        for prompt, (_, n, steps) in zip(prompts, CASES)
    ]
    return got, want, stats, ledger


@pytest.mark.parametrize("case", range(len(CASES)), ids=[f"P{p}-n{n}-s{s}" for p, n, s in CASES])
def test_engine_answers_the_plain_generate_s_tokens_and_reveal_steps(served, case):
    got, want, _, _ = served
    p_len, n_new, steps = CASES[case]
    assert got[case] == want[case]
    assert np.asarray(got[case]["tokens"]).shape == (1, n_new)
    assert len(got[case]["dropped_tokens"][0]) == -(p_len + n_new) % 4
    # denoising_steps forwards reveal a block: no later forward is named
    assert max(got[case]["reveal_step"][0]) <= steps - 1


def test_an_answer_may_hold_the_mask_token_s_id_as_a_real_token(
    model, cfg, scfg, params, weights
):
    """Masked is a flag the engine holds, never ``token == mask_id``. With
    the mask token's id moved to 68, a token these weights like to make,
    this request's first block reveals a real 68 at its forward 0 and runs
    a second forward with it standing there, known: an engine that read
    the id as the flag would mask it again."""
    cfg68 = dict(cfg, assumed=dict(cfg["assumed"], mask_token_id=68))
    prompt = np.random.default_rng(6).integers(0, 128, 10).astype(np.int32)
    eng = _engine(scfg._replace(mask_id=68), params)
    try:
        out = eng.submit(prompt[None], 9, denoising_steps=2, timeout=300)
    finally:
        eng.close()
    got = {k: np.asarray(v).tolist() for k, v in out.items()}
    assert got["tokens"][0][0] == 68 and got["reveal_step"][0][:2] == [0, 0]
    assert got == model.generate(weights, cfg68, prompt, 9, 2, pad_to=PAD)


def test_counters_and_the_ledger_after_the_cases(served):
    got, _, stats, ledger = served
    assert ledger["balanced"] and ledger["drained"]
    tokens = sum(n for _, n, _ in CASES)
    bus = stats["bus"]
    assert stats["tokens_total"] == tokens == bus["tokens"]
    # a block of four costs its denoising forwards and nothing more: every
    # row-forward reveals something, none is a commit alone
    denoise = sum(
        max(block) + 1
        for g, (p, _, _) in zip(got, CASES)
        for block in _blocks([-1] * (p % 4) + g["reveal_step"][0] + g["dropped_reveal_step"][0])
    )
    assert bus["denoise"] == denoise and bus["commit"] == 0
    # a commit's four positions ride in the next block's first forward; the
    # last block of a row is not committed: nothing reads its K/V
    blocks = sum(-(-(p + n) // 4) - p // 4 for p, n, _ in CASES)
    assert bus["positions_denoise"] == 4 * denoise
    assert bus["positions_commit"] == 4 * (blocks - len(CASES))
    assert not stats["fused"] and bus["fused"] == 0
    per_expert = 3 * 64 * 32 * 4
    for path in ("step", "prefill"):
        assert bus[path] > 0 and bus[path] % per_expert == 0


def test_a_block_family_dispatches_ahead_of_what_it_has_read(served):
    """A row's block lives on the device and the host plans its forwards by
    count, so forward n+1 goes out before forward n's answer is fetched:
    with seven requests on four slots nearly every forward went out with an
    earlier one unread (the first of a burst has none before it), nothing
    was left in flight after the drain, and the ledger closes."""
    _, _, stats, ledger = served
    bus = stats["bus"]
    assert bus["ahead_yes"] > 4 * bus["ahead_no"] > 0
    assert bus["arrivals_left"] == 0
    assert ledger["balanced"] and ledger["drained"]


# ── the plan by count, forward by forward ────────────────────────────────

PLANNED = [  # prompt length (a tail of 1-3 known positions), n_new, denoising_steps
    (5, 9, 4), (10, 6, 2), (7, 8, 1), (14, 5, 4), (11, 7, 2), (9, 4, 1),
]


def _gated(eng):
    """Hold the worker thread at its door: everything enqueued before
    ``go`` is queued when the loop starts."""
    go = threading.Event()
    loop = eng._loop
    eng._loop = lambda: (go.wait(30), loop())
    return go


@pytest.fixture(scope="module")
def planned(model, cfg, scfg, params, weights):
    """Six requests queued before a three-slot engine starts, every block
    step's inputs recorded as it is built (``tail``, ``n_reveal``,
    ``commit`` of each live row), and the plain generate's answers."""
    eng = _engine(scfg, params, max_slots=3, slot_buckets=(3,))
    sent, build = [], eng._block_inputs

    def recording(width, live):
        rows = [(row.pending.request_id, i) for i, row in live]
        out = build(width, live)
        tail, n_reveal, commit = (np.asarray(x) for x in out[1])
        sent.append([
            (rid, tail[i].tolist(), int(n_reveal[i]), bool(commit[i]))
            for rid, i in rows
        ])
        return out

    eng._block_inputs = recording
    try:
        go = _gated(eng)
        prompts = [_tokens(200 + i, p) for i, (p, _, _) in enumerate(PLANNED)]
        futures = [
            eng.enqueue(prompt[None], n, denoising_steps=steps)
            for prompt, (_, n, steps) in zip(prompts, PLANNED)
        ]
        ids = [r.pending.request_id for r in eng._queue]
        go.set()
        got = [
            {k: np.asarray(v).tolist() for k, v in f.result(300).items()} for f in futures
        ]
        ledger = eng.ledger()
    finally:
        eng.close()
    want = [
        model.generate(weights, cfg, prompt, n, steps, pad_to=PAD)
        for prompt, (_, n, steps) in zip(prompts, PLANNED)
    ]
    return got, want, sent, ids, prompts, ledger


@pytest.mark.parametrize(
    "case", range(len(PLANNED)), ids=[f"P{p}-n{n}-s{s}" for p, n, s in PLANNED]
)
def test_the_host_plans_by_count_what_fetch_then_build_would_have_sent(planned, case):
    """What the host sends a row, forward by forward, it decides before any
    answer is read; it must be what the answers would have told it. From
    the plain generate's ``reveal_step``: a block's forward ``f`` reveals as
    many positions as are named ``f``, the first forward of every block but
    the row's first carries the commit of the block before it (so none
    follows the row's last, and none is a forward of its own), and only the
    row's first forward brings tokens (the prompt's tail)."""
    got, want, sent, ids, prompts, _ = planned
    p_len, n_new, steps = PLANNED[case]
    assert got[case] == want[case]
    named = (
        [-1] * (p_len % 4) + want[case]["reveal_step"][0]
        + want[case]["dropped_reveal_step"][0]
    )
    expect = [
        (block.count(f), f == 0 and b > 0)
        for b, block in enumerate(_blocks(named))
        for f in range(max(block) + 1)
    ]
    mine = [row for step in sent for row in step if row[0] == ids[case]]
    assert [(n, commit) for _, _, n, commit in mine] == expect
    assert all(0 < n <= 4 // steps for n, _ in expect)
    assert sum(commit for _, commit in expect) == len(_blocks(named)) - 1
    tail = prompts[case][p_len // 4 * 4 :].tolist()
    assert mine[0][1] == tail + [-1] * (4 - len(tail))
    assert all(t == [-1] * 4 for _, t, _, _ in mine[1:])


def test_rows_of_every_denoising_step_shared_the_planned_dispatches(planned):
    _, _, sent, ids, _, ledger = planned
    steps_of = {rid: steps for rid, (_, _, steps) in zip(ids, PLANNED)}
    assert any({steps_of[r[0]] for r in step} == {4, 2, 1} for step in sent)
    # a dispatch with a committing-and-denoising row beside a plain
    # denoising one: every row of it reveals something
    assert any({commit for _, _, _, commit in step} == {True, False} for step in sent)
    assert all(n > 0 for step in sent for _, _, n, _ in step)
    assert ledger["balanced"] and ledger["drained"]


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_row_s_pages_hold_the_k_v_of_a_prefill_over_what_it_committed(
    scfg, params, steps
):
    """A prompt of 22 (its tail opens a block midway) and 13 new tokens (the
    last falls inside a block): nine blocks are denoised, the first eight
    committed, each in the next block's first forward. What the row's
    pages hold of those 32 positions when it is answered is what a prefill
    of the same 32 tokens writes: no commit was lost, none ran over a
    block that was not whole, none landed a block off."""
    eng = _engine(scfg, params, max_slots=2, slot_buckets=(2,))
    pages, release = [], eng._release_row

    def noting(slot, row):
        pages.extend(row.pages)
        release(slot, row)

    eng._release_row = noting
    prompt = _tokens(600 + steps, 22)
    try:
        out = eng.submit(prompt[None], 13, denoising_steps=steps, timeout=300)
        served = np.concatenate([prompt, np.asarray(out["tokens"])[0]])[:32]
        assert len(pages) == 3 and eng.ledger()["balanced"]
        got = [_rows_of(pool, pages)[:, :32] for pool in (eng._k, eng._v)]
    finally:
        eng.close()
    cache = sdar_moe.init_paged_cache(scfg, 1, 4, PAGE)
    table = jnp.asarray([[1, 2, 3]], jnp.int32)
    _, cache, _ = jax.jit(sdar_moe.paged_prefill_chunk, static_argnames=("cfg",))(
        params, cache, table, jnp.int32(0), jnp.asarray(served), jnp.int32(0),
        jnp.int32(32), cfg=scfg,
    )
    for mine, clean in zip(got, (cache.k, cache.v)):
        assert float(np.abs(mine - _rows_of(clean, [1, 2, 3])[:, :32]).max()) <= TOL


def test_a_slot_is_refilled_while_its_last_row_s_forward_is_unread(
    model, cfg, scfg, params, weights
):
    """Two slots, three requests queued. The short row leaves slot 0 when
    its last forward is LAUNCHED; the third request is admitted into that
    slot (its prefill opens the slot's block on the device) while that
    forward's answer is still in flight, beside a long row that goes on.
    All three are answered as the plain generate answers them."""
    eng = _engine(scfg, params, max_slots=2, slot_buckets=(2,))
    seen, assign = [], eng._assign_pages

    def watching(slot, row):
        unread = [r for a in eng._arrivals if a.decode for r in a.rows]
        seen.append((slot, [(len(r.out), r.scheduled, r.n_new) for r in unread]))
        return assign(slot, row)

    eng._assign_pages = watching
    cases = [(9, 6, 2), (6, 24, 4), (7, 5, 4)]
    try:
        go = _gated(eng)
        prompts = [_tokens(300 + i, p) for i, (p, _, _) in enumerate(cases)]
        futures = [
            eng.enqueue(prompt[None], n, denoising_steps=steps)
            for prompt, (_, n, steps) in zip(prompts, cases)
        ]
        go.set()
        got = [
            {k: np.asarray(v).tolist() for k, v in f.result(300).items()} for f in futures
        ]
        assert not eng._arrivals
        ledger = eng.ledger()
    finally:
        eng.close()
    assert seen[:2] == [(0, []), (1, [])] and len(seen) == 3
    # the short row's slot again, while the step that made its last block
    # whole is unread: every token scheduled, the last not yet on the host
    slot, unread = seen[2]
    assert slot == 0 and len(unread) == 2
    arrived, scheduled, n_new = unread[0]
    assert scheduled == n_new == 6 and arrived < n_new
    for answer, prompt, (_, n, steps) in zip(got, prompts, cases):
        assert answer == model.generate(weights, cfg, prompt, n, steps, pad_to=PAD)
    assert ledger["balanced"] and ledger["drained"]


class _Poisoned:
    """A program's answer whose error surfaces when the host fetches it."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("injected device failure")


@pytest.mark.parametrize("when", ["call", "fetch"])
def test_a_failed_block_forward_leaves_a_fresh_block_and_a_balanced_ledger(
    model, cfg, scfg, params, weights, when
):
    """The third block step, the second block's first forward with the
    first block's commit riding in it, RUNS (the donated cache and the
    block beside it are consumed) and then raises at its call, or hands
    back an answer that raises when it is fetched, a dispatch later; either
    way a forward is in flight when the failure lands. Every pending fails typed, the engine
    holds fresh zeroed buffers (the block's two arrays among them), and the
    next request equals the plain generate."""
    eng = _engine(scfg, params, max_slots=1, slot_buckets=(1,))
    try:
        builder = eng.programs.paged_block_step
        calls = []

        def failing(width):
            fn = builder(width)

            def run_then_fail(*args):
                result = fn(*args)
                calls.append(bool(np.asarray(args[-1])[0]))  # its commit flag
                if len(calls) != 3:  # it fails once
                    return result
                if when == "call":
                    raise RuntimeError("injected device failure")
                return (_Poisoned(), *result[1:])

            return run_then_fail

        eng.programs.paged_block_step = failing
        go = _gated(eng)
        futures = [
            eng.enqueue(_tokens(400 + i, 9)[None], 8, denoising_steps=2) for i in range(2)
        ]
        go.set()
        for future in futures:
            with pytest.raises(E.PyGridError, match="engine error"):
                future.result(timeout=60)
        eng.programs.paged_block_step = builder
        # three masked positions two a forward, then the fused forward
        assert calls == [False, False, True, False][: 3 if when == "call" else 4]
        assert not eng._arrivals
        tokens, masked = eng._last
        assert tokens.shape == masked.shape == (1, 4) and masked.dtype == bool
        for arr in (eng._k, eng._v, eng._pos, tokens, masked):
            assert not arr.is_deleted()
            assert not np.asarray(jnp.abs(arr).sum())
        stats = eng.stats()
        assert stats["live_slots"] == 0 and stats["queue_depth"] == 0
        assert stats["kv_blocks_free"] == stats["kv_blocks_total"]
        prompt = _tokens(410, 10)
        out = eng.submit(prompt[None], 7, denoising_steps=2, timeout=300)
        got = {k: np.asarray(v).tolist() for k, v in out.items()}
        assert got == model.generate(weights, cfg, prompt, 7, 2, pad_to=PAD)
        led = eng.ledger()
        assert led["drained"] and led["balanced"], led
    finally:
        eng.close()


def test_close_reads_the_block_forward_in_flight_and_fails_the_rest(
    model, cfg, scfg, params, weights
):
    """Two rows in two slots, a quantum of one step. The first step makes
    the short row's only block whole (all of its tokens scheduled: it
    leaves its slot) and is the long row's first of many; the engine is
    closed with that step's answer unread. The thread reads it before it
    leaves, so the short row is answered, right; the long row fails typed;
    nothing is left in flight and every page is back."""
    eng = _engine(scfg, params, max_slots=2, slot_buckets=(2,), quantum=1)
    stepped, step = threading.Event(), eng._step

    def step_then_wait():
        freed = step()
        stepped.set()
        deadline = time.monotonic() + 30
        while eng._running and time.monotonic() < deadline:
            time.sleep(0.001)
        return freed

    eng._step = step_then_wait
    try:
        go = _gated(eng)
        short, long_ = _tokens(500, 8), _tokens(501, 9)
        first = eng.enqueue(short[None], 4, denoising_steps=1)
        second = eng.enqueue(long_[None], 12, denoising_steps=4)
        go.set()
        assert stepped.wait(60)
        in_flight = [a for a in eng._arrivals if a.decode]
        assert len(in_flight) == 1 and not first.done()
    finally:
        eng.close()
    got = {k: np.asarray(v).tolist() for k, v in first.result(timeout=10).items()}
    assert got == model.generate(weights, cfg, short, 4, 1, pad_to=PAD)
    with pytest.raises(E.PyGridError, match="closed"):
        second.result(timeout=10)
    assert not eng._arrivals
    led = eng.ledger()
    assert led["drained"] and led["balanced"], led


def test_typed_errors_at_the_engine(scfg, params):
    eng = _engine(scfg, params)
    try:
        for bad in ({"denoising_steps": 3}, {"denoising_steps": 0}, {"temperature": 0.5}):
            with pytest.raises(E.PyGridError, match="denoising_steps"):
                eng.enqueue(_tokens(0, 8)[None], 4, **bad)
        with pytest.raises(E.PyGridError, match="exceeds max_len"):
            eng.enqueue(_tokens(0, 100)[None], 29)
    finally:
        eng.close()
