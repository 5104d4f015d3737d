"""Continuous-batching engine contracts (pygrid_tpu/serving).

The three that matter: (1) greedy tokens from the batched slot engine
are BIT-IDENTICAL to single-request ``decode.generate`` — no cross-slot
leakage through the shared cache, no numeric drift from batching; (2)
request-shape variety (prompt length, ``n_new``, temperature, seed)
within one bucket set triggers ZERO recompiles — the pathology the
engine replaces jitted one program per distinct ``n_new``; (3) the
bounded queue answers typed backpressure instead of piling up.
"""

from __future__ import annotations

import ast
import re
import threading
from pathlib import Path

import numpy as np
import pytest

import jax

from pygrid_tpu.models import decode
from pygrid_tpu.models import transformer as T
from pygrid_tpu.serving import EngineConfig, GenerationEngine, ServingManager
from pygrid_tpu.utils import exceptions as E

CFG = T.TransformerConfig(
    vocab=31, d_model=16, n_heads=2, n_layers=1, d_ff=32, max_len=32
)


@pytest.fixture(scope="module")
def params():
    return T.init(jax.random.PRNGKey(5), CFG)


@pytest.fixture(scope="module")
def engine(params):
    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(max_slots=4, slot_buckets=(1, 2, 4), min_prompt_bucket=8),
        model_id="unit",
    )
    yield eng
    eng.close()


def _ref(params, prompt, n_new, **kw):
    return np.asarray(
        decode.generate(params, np.asarray(prompt, np.int32), n_new, CFG, **kw)
    )


def test_greedy_bit_identical_to_single_request(engine, params):
    prompts = [[3, 5, 2, 9, 11], [1, 2], [7, 8, 9], [4]]
    n_news = [6, 3, 5, 8]
    for p, n in zip(prompts, n_news):
        got = engine.submit(np.array([p]), n)
        np.testing.assert_array_equal(got, _ref(params, [p], n))


def test_multi_row_prompt_reassembles_in_order(engine, params):
    prompt = np.array([[3, 5, 2], [1, 2, 4], [9, 9, 1]])
    got = engine.submit(prompt, 4)
    np.testing.assert_array_equal(got, _ref(params, prompt, 4))


def test_concurrent_mixed_requests_no_cross_slot_leakage(engine, params):
    """More concurrent requests than slots, mixed prompt lengths and
    n_new: every result equals its sequential single-request twin —
    the shared cache leaks nothing across slots, and queueing past the
    slot count still serves everyone."""
    cases = [
        (np.array([[2 + i, 5, 1, 7][: 1 + i % 4]]), 2 + (i * 3) % 7)
        for i in range(10)
    ]
    results: list = [None] * len(cases)

    def go(i):
        prompt, n = cases[i]
        results[i] = engine.submit(prompt, n)

    threads = [
        threading.Thread(target=go, args=(i,)) for i in range(len(cases))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for (prompt, n), got in zip(cases, results):
        np.testing.assert_array_equal(got, _ref(params, prompt, n))


def test_shape_variety_within_buckets_zero_recompiles(engine, params):
    """The tentpole compile contract: after warmup, varying n_new,
    prompt length (within one prompt bucket), temperature and seed
    compiles NOTHING."""
    engine.warmup(prompt_lens=(1, 8))
    before = engine.compile_count()
    for i, (p_len, n_new) in enumerate(
        [(1, 2), (3, 9), (5, 4), (8, 1), (2, 7), (6, 3)]
    ):
        prompt = np.full((1, p_len), 1 + i % 7)
        temp = 0.0 if i % 2 == 0 else 0.7
        got = engine.submit(prompt, n_new, temperature=temp, seed=i)
        assert got.shape == (1, n_new)
    assert engine.compile_count() == before, (
        "request-shape variety inside one bucket must not recompile"
    )
    # and at the jit layer: every program traced exactly once (no
    # silent retraces from shape/dtype drift at the engine call sites)
    assert engine.programs.trace_count() == engine.compile_count()


def test_sampling_reproducible_and_seed_sensitive(engine, params):
    prompt = np.array([[3, 5, 2]])
    a = engine.submit(prompt, 8, temperature=0.9, seed=123)
    b = engine.submit(prompt, 8, temperature=0.9, seed=123)
    np.testing.assert_array_equal(a, b)
    assert (a >= 0).all() and (a < CFG.vocab).all()
    outs = {
        tuple(engine.submit(prompt, 8, temperature=0.9, seed=s)[0])
        for s in range(6)
    }
    assert len(outs) > 1, "different seeds must be able to differ"


def test_queue_backpressure_is_typed_and_recoverable(params):
    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(
            max_slots=1, slot_buckets=(1,), min_prompt_bucket=8, max_queue=2
        ),
        model_id="bp",
    )
    try:
        eng.warmup(prompt_lens=(2,))
        futures = [
            eng.enqueue(np.array([[1, 2]]), 24) for _ in range(2)
        ]
        with pytest.raises(E.ServerBusyError, match="queue full"):
            # 1 row decoding + 2 queued = at the depth limit
            for _ in range(8):
                futures.append(eng.enqueue(np.array([[1, 2]]), 24))
        for f in futures:
            assert f.result(timeout=60).shape == (1, 24)
        # drained: the engine serves again after shedding load
        assert eng.submit(np.array([[1, 2]]), 2).shape == (1, 2)
    finally:
        eng.close()


def test_oversized_batch_is_permanent_defect_not_busy(params):
    """A [B, P] prompt with more rows than the queue can ever hold must
    bounce as a non-retryable PyGridError — ServerBusyError would tell
    the client to retry a permanent condition forever."""
    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(
            max_slots=1, slot_buckets=(1,), min_prompt_bucket=8, max_queue=3
        ),
    )
    try:
        with pytest.raises(E.PyGridError, match="queue capacity") as exc:
            eng.enqueue(np.ones((4, 2), np.int32), 2)
        assert not isinstance(exc.value, E.ServerBusyError)
    finally:
        eng.close()


def test_bf16_cache_greedy_matches_generate(params):
    """The bit-identical contract must survive a narrowed cache dtype:
    prefill_slot rounds k/v through the cache dtype before attending,
    exactly like the batch prefill decode.generate runs."""
    import jax.numpy as jnp

    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(
            max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8,
            cache_dtype=jnp.bfloat16,
        ),
        model_id="bf16",
    )
    try:
        for prompt, n in ([[3, 5, 2, 9]], 6), ([[1, 2]], 4):
            got = eng.submit(np.array(prompt), n)
            ref = _ref(params, prompt, n, cache_dtype=jnp.bfloat16)
            np.testing.assert_array_equal(got, ref)
    finally:
        eng.close()


def test_manager_rebuilds_engine_on_rehost():
    """Re-hosting a model id constructs a new HostedModel — the manager
    must drop the stale engine (old params) and serve the new bundle."""
    from pygrid_tpu.datacentric.model_storage import HostedModel

    params_a = T.init(jax.random.PRNGKey(1), CFG)
    params_b = T.init(jax.random.PRNGKey(2), CFG)
    mgr = ServingManager(
        EngineConfig(max_slots=1, slot_buckets=(1,), min_prompt_bucket=8)
    )
    try:
        hosted_a = HostedModel("m", decode.bundle(CFG, params_a))
        hosted_b = HostedModel("m", decode.bundle(CFG, params_b))
        eng_a = mgr.engine_for("m", hosted_a)
        assert mgr.engine_for("m", hosted_a) is eng_a
        got_a = eng_a.submit(np.array([[3, 5]]), 4)
        np.testing.assert_array_equal(got_a, _ref(params_a, [[3, 5]], 4))
        eng_b = mgr.engine_for("m", hosted_b)
        assert eng_b is not eng_a
        got_b = eng_b.submit(np.array([[3, 5]]), 4)
        np.testing.assert_array_equal(got_b, _ref(params_b, [[3, 5]], 4))
        mgr.evict("m")
        assert mgr.stats() == []
    finally:
        mgr.close()


def _ahead(answer: str) -> float:
    from pygrid_tpu import telemetry

    return sum(
        v for (name, labels), v in telemetry.counters().items()
        if name == "serving_dispatches_total" and ("ahead", answer) in labels
    )


def _gated(eng):
    """Hold the worker thread at its door: everything enqueued before
    ``go`` is queued when the loop starts, so the backlog is the same in
    every run."""
    go = threading.Event()
    loop = eng._loop
    eng._loop = lambda: (go.wait(30), loop())
    return go


class _Poisoned:
    """A program's answer whose error surfaces when the host fetches it:
    what a device fault looks like to a loop that reads a dispatch late."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("injected device failure")


@pytest.mark.parametrize("when", ["call", "fetch"])
@pytest.mark.parametrize(
    "program, fused",
    [
        ("paged_prefill", True),
        ("paged_decode", False),
        ("paged_decode_fused", True),
    ],
)
def test_engine_recovers_after_device_loop_failure(params, program, fused, when):
    """Whichever of the engine's three programs fails: it RUNS (so the
    donated cache buffers are consumed) and then raises at its call, or
    hands back an answer that raises when it is fetched, a dispatch
    later. Two requests are queued before the loop starts, of one token
    and of two, so a row has left its slot with its token still in flight
    when the failure lands: every pending that was not answered fails
    typed (none hangs), an answer that was delivered is right, the engine
    holds a fresh zeroed cache instead of the deleted arrays, the next
    request equals ``generate()`` and the block ledger closes."""
    import jax.numpy as jnp

    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(
            max_slots=1, slot_buckets=(1,), min_prompt_bucket=8,
            block_size=8, fused=fused,
        ),
        model_id=f"boom-{program}-{when}",
    )
    try:
        builder = getattr(eng.programs, program)
        calls = []

        def failing(*key):
            fn = builder(*key)

            def run_then_fail(*args):
                result = fn(*args)
                calls.append(key)
                if len(calls) > 1:  # it fails once
                    return result
                if when == "call":
                    raise RuntimeError("injected device failure")
                return (_Poisoned(), *result[1:])

            return run_then_fail

        setattr(eng.programs, program, failing)
        go = _gated(eng)
        futures = [eng.enqueue(np.array([[1, 2]]), n) for n in (1, 2)]
        go.set()
        failed = 0
        for future, n in zip(futures, (1, 2)):
            try:
                got = future.result(timeout=30)
            except E.PyGridError as err:
                assert "engine error" in str(err)
                failed += 1
            else:
                np.testing.assert_array_equal(got, _ref(params, [[1, 2]], n))
        # the request the failing program served never got its answer
        assert failed >= 1 and not eng._arrivals
        setattr(eng.programs, program, builder)
        assert calls, "the named program is the one that failed"
        if when == "call":
            assert len(calls) == 1  # nothing was launched behind it
        for arr in (eng._k, eng._v, eng._pos, eng._last):
            assert not arr.is_deleted()
            assert not np.asarray(jnp.abs(arr).sum())
        stats = eng.stats()
        assert stats["live_slots"] == 0 and stats["queue_depth"] == 0
        assert stats["kv_blocks_free"] == stats["kv_blocks_total"]
        got = eng.submit(np.array([[1, 2]]), 4, timeout=60)
        np.testing.assert_array_equal(got, _ref(params, [[1, 2]], 4))
        led = eng.ledger()
        assert led["drained"] and led["balanced"], led
    finally:
        eng.close()


# ── dispatching ahead of what the host has read ──────────────────────────


#: more requests than slots, ``n_new`` of 1 and 2 among them: a row that
#: leaves its slot at its own prefill, one that leaves a step later
BACKLOG = [
    ([3, 5, 2, 9, 11], 6), ([1, 2], 1), ([7, 8, 9], 2), ([4], 9),
    ([6, 6, 6, 1], 1), ([2, 4, 6, 8, 10, 12, 14], 5), ([9], 2), ([5, 3], 11),
]


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("fused", [False, True], ids=["step", "fused"])
def test_dispatch_ahead_serves_generates_tokens_under_a_backlog(
    params, fused, temperature
):
    """Eight requests on two slots. Step n+1 is launched before step n's
    tokens are on the host and a freed slot's prefill goes out behind a
    step still in flight, so what decides each token (the device's last
    token, the key of the token being SCHEDULED, the row's place) never
    comes from ``row.out``. Every answer is bit-identical to
    ``generate()`` alone, greedy and sampled per seed."""
    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(
            max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8,
            block_size=8, fused=fused,
        ),
        model_id="ahead",
    )
    try:
        prefill = eng.programs.paged_prefill
        behind_a_step = []

        def watched(bucket):
            fn = prefill(bucket)

            def call(*args):
                behind_a_step.append(any(a.decode for a in eng._arrivals))
                return fn(*args)

            return call

        eng.programs.paged_prefill = watched
        go = _gated(eng)
        ahead = _ahead("yes")
        futures = [
            eng.enqueue(np.array([p]), n, temperature=temperature, seed=40 + i)
            for i, (p, n) in enumerate(BACKLOG)
        ]
        go.set()
        for i, ((p, n), future) in enumerate(zip(BACKLOG, futures)):
            kw = (
                dict(temperature=temperature, key=jax.random.PRNGKey(40 + i))
                if temperature else {}
            )
            np.testing.assert_array_equal(
                future.result(120), _ref(params, [p], n, **kw)
            )
        assert len(behind_a_step) == len(BACKLOG) and any(behind_a_step)
        assert _ahead("yes") > ahead
        led = eng.ledger()
        assert led["drained"] and led["balanced"], led
    finally:
        eng.close()


def test_a_table_handed_to_a_program_is_not_the_mirror_the_host_edits(params):
    """``jnp.asarray`` may hand the device the numpy array's own memory
    (on the CPU it does, for some alignments), and a program in flight
    reads its block table while the host zeroes a retired row's entries
    for the next: the device gets a copy that nobody writes again."""
    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(max_slots=2, slot_buckets=(1, 2), block_size=4),
        model_id="table",
    )
    try:
        assert eng._table_np.shape == (2, 8)
        mirrors = []  # kept: each new mirror at another address
        for _ in range(32):  # whatever alignment the allocator gives
            mirrors.append(eng._table_np)
            eng._table_np = np.zeros_like(eng._table_np)
            eng._table_np[0, :3] = (5, 6, 7)
            eng._table_dirty = True
            handed = eng._table()
            eng._table_np[0, :] = 0  # the row retires
            assert np.asarray(handed)[0].tolist() == [5, 6, 7, 0, 0, 0, 0, 0]
    finally:
        eng.close()


def test_a_bursts_last_answer_needs_no_further_request(params):
    """Nothing stays in flight where nobody would collect it: with the
    last row's last token scheduled the loop has nothing left to launch,
    and it fetches what is in flight before it waits for work."""
    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(
            max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8,
            block_size=8, fused=False,
        ),
        model_id="burst",
    )
    try:
        go = _gated(eng)
        futures = [eng.enqueue(np.array([p]), n) for p, n in BACKLOG]
        go.set()
        for (p, n), future in zip(BACKLOG, futures):
            np.testing.assert_array_equal(
                future.result(120), _ref(params, [p], n)
            )
        assert not eng._arrivals
        stats = eng.stats()
        assert stats["tokens_total"] == sum(n for _, n in BACKLOG)
        assert stats["live_slots"] == 0 and eng.ledger()["drained"]
    finally:
        eng.close()


def test_close_answers_a_row_whose_last_token_is_in_flight(params):
    """``close()`` lands between two passes of the loop: row A's last
    token was scheduled by the pass's last step and is still in flight,
    row B is mid-generation. The thread collects before it leaves, so A
    is answered (its tokens were made); B, which is not finished, fails
    typed."""
    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(
            max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8,
            block_size=8, fused=False, quantum=2,
        ),
        model_id="closing",
    )
    reached, release = threading.Event(), threading.Event()
    step, steps = eng._step, []

    def held_after_the_pass():
        freed = step()
        steps.append(freed)
        if len(steps) == 2:  # the quantum's last step: A just retired
            reached.set()
            release.wait(30)
        return freed

    eng._step = held_after_the_pass
    go = _gated(eng)
    a = eng.enqueue(np.array([[3, 5, 2]]), 3)  # prefill + two steps
    b = eng.enqueue(np.array([[7, 8]]), 20)
    go.set()
    assert reached.wait(60)
    assert not a.done() and [r.pending.future for x in eng._arrivals for r in x.rows]
    closer = threading.Thread(target=eng.close)
    closer.start()
    while eng._running:
        closer.join(0.01)
    release.set()
    closer.join(30)
    assert not closer.is_alive()
    np.testing.assert_array_equal(a.result(0), _ref(params, [[3, 5, 2]], 3))
    with pytest.raises(E.PyGridError, match="closed"):
        b.result(0)
    assert not eng._arrivals and eng.ledger()["balanced"]


def test_compiled_surface_is_buckets_plus_two_programs_a_width(params):
    """What the engine ever compiles, counted: one prefill program per
    prompt bucket asked for, and per decode width one step program and
    one fused scan. Eight distinct ``n_new`` afterwards add none —
    ``n_new`` is a host loop bound, never a shape."""
    widths = (1, 2, 4)
    eng = GenerationEngine(
        CFG,
        params,
        EngineConfig(max_slots=4, slot_buckets=widths, min_prompt_bucket=8),
        model_id="surface",
    )
    try:
        eng.warmup(prompt_lens=(3, 8, 12, 30))  # buckets 8, 8, 16, 32
        assert eng.compile_count() == 3 + 2 * len(widths)
        built = eng.programs
        assert (
            len(built._paged_prefill),
            len(built._paged_decode),
            len(built._paged_fused),
        ) == (3, len(widths), len(widths))
        for n_new in (1, 2, 3, 5, 7, 9, 11, 13):
            got = eng.submit(np.array([[4, 2, 6]]), n_new)
            np.testing.assert_array_equal(
                got, _ref(params, [[4, 2, 6]], n_new)
            )
        assert eng.compile_count() == 3 + 2 * len(widths)
        assert eng.programs.trace_count() == eng.compile_count()
    finally:
        eng.close()


def test_closed_engine_rejects_typed(params):
    eng = GenerationEngine(CFG, params, EngineConfig(max_slots=1))
    eng.close()
    with pytest.raises(E.PyGridError, match="closed"):
        eng.enqueue(np.array([[1]]), 2)


def test_serving_telemetry_families_flow(engine):
    """The engine feeds the PR-2 bus: request/token counters and the
    TTFT / per-token / occupancy histograms all carry observations."""
    from pygrid_tpu import telemetry

    engine.submit(np.array([[1, 2, 3]]), 3)
    counters = {name for (name, _), _ in telemetry.counters().items()}
    assert "serving_requests_total" in counters
    assert "serving_tokens_total" in counters
    assert "serving_compiles_total" in counters
    hists = {name for (name, _), _ in telemetry.histograms().items()}
    for family in (
        "serving_ttft_seconds",
        "serving_token_seconds",
        "serving_prefill_seconds",
        "serving_queue_wait_seconds",
        "serving_batch_occupancy",
    ):
        assert family in hists, family


# ── what a model family has to write, and the options ratchet ────────────

#: everything the node and the serving package ask of a family's module
FAMILY_CONTRACT = {
    "PagedCache", "init_paged_cache", "paged_prefill_chunk",
    "paged_decode_step", "kv_layers", "kv_heads", "kv_kernel",
    "state_bytes_per_slot", "cache_elements", "RECURRENT", "BLOCK_LEN",
}

_PACKAGE = Path(__file__).resolve().parents[2] / "pygrid_tpu"


def _family_attributes_used(path: Path) -> set[str]:
    """Attributes read off a family module in ``path``: off
    ``self._family``, off ``decode.family_of(...)``, or off a local name
    bound to either in the enclosing function."""

    def is_family(node) -> bool:
        if isinstance(node, ast.Call):
            node = node.func
            return isinstance(node, ast.Attribute) and node.attr == "family_of"
        return isinstance(node, ast.Attribute) and node.attr == "_family"

    used: set[str] = set()
    tree = ast.parse(path.read_text())
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        aliases: set[str] = set()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            for target in node.targets:
                pairs = (
                    zip(target.elts, node.value.elts)
                    if isinstance(target, ast.Tuple)
                    and isinstance(node.value, ast.Tuple)
                    else [(target, node.value)]
                )
                aliases |= {
                    t.id for t, v in pairs
                    if isinstance(t, ast.Name) and is_family(v)
                }
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) and (
                is_family(node.value)
                or isinstance(node.value, ast.Name)
                and node.value.id in aliases
            ):
                used.add(node.attr)
    return used


@pytest.mark.parametrize(
    "family",
    ["decode", "jamba", "sdar_moe", "solar_open2", "brumby", "lfm2_moe"],
)
def test_a_family_module_is_these_ten_names_and_nothing_else(family):
    """What a further family has to write, pinned: the module exposes the
    contract (the ten names and, since the block-diffusion family,
    ``BLOCK_LEN``: the positions a row's forward carries), and the node
    and the serving package (read from their source) reach a family
    through no other attribute."""
    import importlib

    module = importlib.import_module(f"pygrid_tpu.models.{family}")
    missing = {n for n in FAMILY_CONTRACT if not hasattr(module, n)}
    assert not missing, f"models/{family}.py lacks {sorted(missing)}"
    assert module.PagedCache._fields[:3] == ("k", "v", "pos")
    used = set().union(*(
        _family_attributes_used(p)
        for d in ("serving", "node") for p in (_PACKAGE / d).glob("*.py")
    ))
    assert used == FAMILY_CONTRACT, sorted(used ^ FAMILY_CONTRACT)


def test_ratchet_environment_knobs_only_fall():
    """The distinct ``PYGRID_*`` names the package reads. The number
    only falls: a PR that needs a new one takes an old one out, or says
    in ROADMAP.md (debt D3) why the ceiling moves."""
    names = sorted({
        name
        for p in _PACKAGE.rglob("*.py")
        for name in re.findall(r"PYGRID_[A-Z0-9_]+", p.read_text())
    })
    assert len(names) <= 36, f"{len(names)} knobs: {names}"


def test_ratchet_engine_config_fields_only_fall():
    """``EngineConfig``'s independently settable fields. The number only
    falls (ROADMAP.md debt D3)."""
    import dataclasses

    fields = [f.name for f in dataclasses.fields(EngineConfig)]
    assert len(fields) <= 13, f"{len(fields)} fields: {fields}"
