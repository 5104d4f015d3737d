"""Paged KV cache contracts (pygrid_tpu/serving/pagedkv + engine paged
path + models/decode paged programs).

The ones that matter: (1) the paged engine's greedy output is
BIT-IDENTICAL to single-request ``generate()`` — including with a
bf16-narrowed cache — so block-table gather/scatter attention adds no
numeric drift; (2) prefix sharing is copy-on-write: a later request's
decode appends never corrupt the shared pages an earlier request (or the
prefix cache) still reads; (3) block refcounts balance EXACTLY — after
mixed complete/failed/busy traffic every block returns to the free list;
(4) admission exhausts the BLOCK POOL, not the slot count: busy is typed
and recoverable, an impossible request is a typed permanent defect.
"""

from __future__ import annotations

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pygrid_tpu.models import decode
from pygrid_tpu.models import transformer as T
from pygrid_tpu.serving import (
    BlockPool,
    DeviceBudget,
    EngineConfig,
    GenerationEngine,
    PrefixCache,
    pagedkv,
)
from pygrid_tpu.utils import exceptions as E

CFG = T.TransformerConfig(
    vocab=31, d_model=16, n_heads=2, n_layers=2, d_ff=32, max_len=32
)


@pytest.fixture(scope="module")
def params():
    return T.init(jax.random.PRNGKey(5), CFG)


def _ref(params, prompt, n_new, **kw):
    return np.asarray(
        decode.generate(params, np.asarray(prompt, np.int32), n_new, CFG, **kw)
    )


def _paged_engine(params, **over):
    kw = dict(
        max_slots=4, slot_buckets=(1, 2, 4), min_prompt_bucket=8,
        block_size=8,
    )
    kw.update(over)
    return GenerationEngine(CFG, params, EngineConfig(**kw), model_id="pg")


# ── allocator / prefix-cache units ───────────────────────────────────────


def test_block_pool_refcounts_and_trash_reservation():
    pool = BlockPool(8)
    assert pool.usable == 7
    got = pool.alloc(3)
    assert got is not None and 0 not in got
    assert pool.free_count() == 4
    assert pool.alloc(5) is None  # all-or-nothing
    pool.incref(got[:1])
    pool.release(got)  # one block keeps a ref
    assert pool.free_count() == 6
    pool.release(got[:1])
    assert pool.free_count() == 7
    with pytest.raises(RuntimeError):
        pool.release(got[:1])  # releasing a free block is a bug, loudly


def test_prefix_cache_match_insert_evict_lru_leaf_first():
    pool = BlockPool(16)
    cache = PrefixCache(pool, block_tokens=4)
    prompt = np.arange(12, dtype=np.int32)  # 2 shareable 4-token pages
    assert cache.probe(prompt) == 0
    pages = pool.alloc(3)
    cache.insert(prompt, pages)
    assert cache.block_count() == 2  # floor((12-1)/4) = 2 full pages
    assert cache.probe(prompt) == 2
    # a prompt sharing only the first page matches one level deep
    other = np.concatenate([prompt[:4], np.array([9, 9, 9, 9, 9], np.int32)])
    assert cache.probe(other) == 1
    matched = cache.match(prompt)
    assert matched == pages[:2]
    pool.release(pages)  # the publishing row completes
    # while a matched reader still shares the chain, eviction refuses
    # to touch it: freeing nothing for the pool while destroying a
    # chain future prompts could hit would be pure loss
    assert not cache.evict_one()
    assert cache.probe(prompt) == 2
    pool.release(matched)  # the reader completes too
    # now evictable, leaf-first: the depth-2 node goes before its parent
    assert cache.evict_one()
    assert cache.probe(prompt) == 1
    assert cache.evict_one()
    assert cache.probe(prompt) == 0
    assert not cache.evict_one()
    assert pool.free_count() == pool.usable  # every ref balanced


def test_device_budget_weight_partition():
    budget = DeviceBudget(
        total_bytes=1000, weights={"a": 3.0, "b": 1.0}
    )
    a = budget.blocks_for("a", bytes_per_block=10)
    assert a == 75  # 3/4 of 1000 bytes at 10 bytes/block
    b = budget.blocks_for("b", bytes_per_block=10)
    assert b == 25
    budget.release("a")
    # re-registration with the slot free gets the full share again
    assert budget.blocks_for("a", bytes_per_block=10) == 75
    # no budget configured → None (engine sizes itself)
    assert DeviceBudget(None).blocks_for("x", 10) is None


def test_block_size_and_knob_resolution(monkeypatch):
    assert pagedkv.resolve_block_size(512) == 64  # default
    assert pagedkv.resolve_block_size(512, 100) == 64  # power-of-two floor
    assert pagedkv.resolve_block_size(32, 64) == 32  # clamped to max_len
    monkeypatch.setenv("PYGRID_KV_BLOCK", "16")
    assert pagedkv.resolve_block_size(512) == 16
    monkeypatch.setenv("PYGRID_KV_BLOCK", "garbage")
    assert pagedkv.resolve_block_size(512) == 64  # never bricks
    assert pagedkv.parse_budget_bytes("256M") == 256 << 20
    assert pagedkv.parse_budget_bytes("1.5K") == 1536
    assert pagedkv.parse_budget_bytes("oops") is None
    assert pagedkv.parse_weights("a=2,b=1,junk,c=x") == {"a": 2.0, "b": 1.0}


def test_default_cache_dtype_is_bf16_on_tpu(monkeypatch):
    """The TPU default: cache_dtype unset → bf16 on a TPU backend
    (decode is bandwidth-bound on the cache sweep), f32 elsewhere."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pagedkv.default_cache_dtype() == jnp.bfloat16
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert pagedkv.default_cache_dtype() == jnp.float32


# ── engine paged path ────────────────────────────────────────────────────


def test_paged_greedy_bit_identical_and_fragmentation_gauges(params):
    eng = _paged_engine(params)
    try:
        for p, n in ([[3, 5, 2, 9, 11]], 6), ([[1, 2]], 3), ([[7]], 8):
            got = eng.submit(np.array(p), n)
            np.testing.assert_array_equal(got, _ref(params, p, n))
        stats = eng.stats()
        assert stats["kv_blocks_free"] >= 0
        assert stats["block_size"] == 8
    finally:
        eng.close()


def test_paged_bf16_cache_parity_with_generate(params):
    """The bf16-default satellite's contract on the PAGED path: a
    cache-dtype-narrowed paged engine stays bit-identical to
    ``generate(cache_dtype=bf16)`` — block-table scatter/gather rounds
    k/v through the cache dtype exactly like the contiguous path."""
    eng = _paged_engine(params, cache_dtype=jnp.bfloat16)
    try:
        for p, n in ([[3, 5, 2, 9]], 6), ([[1, 2]], 4), ([[6, 4, 2, 8, 1, 3]], 5):
            got = eng.submit(np.array(p), n)
            np.testing.assert_array_equal(
                got, _ref(params, p, n, cache_dtype=jnp.bfloat16)
            )
    finally:
        eng.close()


def test_prefix_sharing_copy_on_write_correctness(params):
    """Three requests sharing an 8-token (one-page) prefix with
    different suffixes, then the FIRST prompt again: every output equals
    its single-request twin, so later requests' decode appends never
    leaked into the shared page (copy-on-write held) and the prefix
    cache's page still holds the original k/v."""
    common = [3, 5, 2, 9, 11, 4, 7, 1]  # exactly one 8-token page
    eng = _paged_engine(params, max_slots=4)
    try:
        cases = [
            (common + [6, 2], 5),
            (common + [1], 4),
            (common + [8, 8, 3], 6),
            (common + [6, 2], 5),  # re-read of the (aged) shared page
        ]
        for i, (p, n) in enumerate(cases):
            got = eng.submit(np.array([p]), n)
            np.testing.assert_array_equal(got, _ref(params, [p], n))
        stats = eng.stats()
        assert stats["prefix_hits"] >= 3, stats
        assert stats["prefix_tokens_saved"] >= 3 * 8, stats
    finally:
        eng.close()


def test_prefix_sharing_concurrent_hits_match_reference(params):
    common = [3, 5, 2, 9, 11, 4, 7, 1]
    eng = _paged_engine(params)
    try:
        first = eng.submit(np.array([common + [2]]), 4)
        np.testing.assert_array_equal(
            first, _ref(params, [common + [2]], 4)
        )
        cases = [(common + [10 + i], 3 + i % 4) for i in range(6)]
        results: list = [None] * len(cases)

        def go(i):
            p, n = cases[i]
            results[i] = eng.submit(np.array([p]), n)

        threads = [
            threading.Thread(target=go, args=(i,))
            for i in range(len(cases))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for (p, n), got in zip(cases, results):
            np.testing.assert_array_equal(got, _ref(params, [p], n))
        assert eng.stats()["prefix_hits"] >= len(cases)
    finally:
        eng.close()


def test_busy_fires_on_block_exhaustion_not_slots(params):
    """Plenty of slots, tiny pool, no overcommit: the 2nd request's
    worst-case page demand exceeds the pool → typed ServerBusyError
    naming the block pool — and the engine recovers once drained."""
    eng = _paged_engine(
        params, max_slots=4, num_blocks=3, kv_overcommit=1.0,
        max_queue=64,
    )
    try:
        eng.warmup(prompt_lens=(2,))
        futures = [eng.enqueue(np.array([[1, 2]]), 12)]  # 14 tok = 2 pages
        with pytest.raises(E.ServerBusyError, match="KV block pool"):
            for _ in range(8):
                futures.append(eng.enqueue(np.array([[1, 2]]), 12))
        for f in futures:
            assert f.result(timeout=60).shape == (1, 12)
        # drained: demand refunded, the engine serves again
        assert eng.submit(np.array([[1, 2]]), 2).shape == (1, 2)
    finally:
        eng.close()


def test_impossible_request_is_typed_defect_not_busy(params):
    eng = _paged_engine(params, num_blocks=2)  # 1 usable block = 8 tokens
    try:
        with pytest.raises(E.PyGridError, match="KV blocks") as exc:
            eng.enqueue(np.array([[1, 2, 3]]), 20)  # needs 3 pages
        assert not isinstance(exc.value, E.ServerBusyError)
    finally:
        eng.close()


def test_block_refcount_leak_free_after_mixed_outcomes(params):
    """The leak test the ISSUE names: complete + failed + busy traffic,
    then all blocks are back — free + prefix-cache-held == usable, and
    after clearing the cache the free list holds EVERY usable block."""
    eng = _paged_engine(
        params, max_slots=2, num_blocks=7, kv_overcommit=1.0,
        max_queue=8,
    )
    try:
        eng.warmup(prompt_lens=(4, 2))
        # completed requests (the first publishes prefix pages)
        for p, n in ([[3, 5, 2, 9, 1, 7, 4, 8, 6]], 5), ([[1, 2]], 3):
            np.testing.assert_array_equal(
                eng.submit(np.array(p), n), _ref(params, p, n)
            )
        # busy outcome: flood past the no-overcommit demand bound
        accepted = []
        with pytest.raises(E.ServerBusyError):
            for _ in range(32):
                accepted.append(eng.enqueue(np.array([[1, 2, 3]]), 18))
        for f in accepted:
            assert f.result(timeout=60).shape == (1, 18)
        # failed outcome: injected device failure → _fail_all resets the
        # pool AND the prefix cache (stale device data) exactly
        original = eng.programs.paged_prefill

        def boom(bucket):
            raise RuntimeError("injected device failure")

        eng.programs.paged_prefill = boom
        with pytest.raises(E.PyGridError, match="engine error"):
            eng.submit(np.array([[4, 4]]), 2, timeout=30)
        eng.programs.paged_prefill = original
        # wait out the failed flood: every future resolves (failed)
        # before accounting is checked
        import time as _t

        deadline = _t.monotonic() + 30
        while _t.monotonic() < deadline:
            s = eng.stats()
            if s["live_slots"] == 0 and s["queue_depth"] == 0:
                break
            _t.sleep(0.05)
        # serve again after the failure, then audit the ledger
        np.testing.assert_array_equal(
            eng.submit(np.array([[1, 2]]), 2, timeout=60),
            _ref(params, [[1, 2]], 2),
        )
        stats = eng.stats()
        assert stats["live_slots"] == 0 and stats["queue_depth"] == 0
        pool, prefix = eng._pool, eng._prefix
        assert pool.free_count() + prefix.block_count() == pool.usable
        assert stats["kv_demand_pages"] == 0
        prefix.clear()
        assert pool.free_count() == pool.usable  # every block returned
    finally:
        eng.close()


def test_paged_zero_recompiles_across_prefix_variety(params):
    """Shape variety AND prefix-hit variety (start 0 vs block-aligned
    offsets) ride the same compiled programs: traced start/length, one
    program per chunk bucket / width bucket."""
    eng = _paged_engine(params)
    try:
        eng.warmup(prompt_lens=(1, 8, 10))
        before = eng.compile_count()
        common = [3, 5, 2, 9, 11, 4, 7, 1]
        for i, (p, n) in enumerate(
            [
                ([1, 2], 3), (common + [5], 4), (common + [2, 2], 6),
                ([4], 7), (common + [9], 2), ([6, 6, 6], 5),
            ]
        ):
            got = eng.submit(
                np.array([p]), n,
                temperature=0.0 if i % 2 == 0 else 0.8, seed=i,
            )
            assert got.shape == (1, n)
        assert eng.compile_count() == before
        assert eng.programs.trace_count() == eng.compile_count()
    finally:
        eng.close()


def test_pool_of_s_full_slots_holds_three_times_as_many_short_requests(params):
    """What paging buys, as a count of blocks: a pool with the bytes S
    full-length requests would take (``1 + S × max_pages`` blocks)
    keeps at least 3 S short requests (prompt + n_new ≤ max_len / 4)
    LIVE AT ONCE — none parked for want of a page, none answered
    busy. The first prefill is held until every request is queued, so
    one admission pass sees them all; liveness is read at the first
    decode dispatch after it."""
    S = 2
    max_pages = CFG.max_len // 8
    eng = _paged_engine(
        params, max_slots=4 * S, slot_buckets=(4 * S,),
        num_blocks=1 + S * max_pages,
    )
    gate = threading.Event()
    seen: list[dict] = []
    prefill = eng.programs.paged_prefill
    step, scan = eng.programs.paged_decode, eng.programs.paged_decode_fused

    def held_prefill(bucket):
        gate.wait(30)
        return prefill(bucket)

    def watched(builder):
        def build(*key):
            seen.append(eng.stats())
            return builder(*key)

        return build

    eng.programs.paged_prefill = held_prefill
    eng.programs.paged_decode = watched(step)
    eng.programs.paged_decode_fused = watched(scan)
    try:
        short = CFG.max_len // 4
        futures = [
            eng.enqueue(np.array([[1 + i, 2, 3]]), short - 3)
            for i in range(4 * S)
        ]  # no ServerBusyError: none answered busy
        gate.set()
        for i, f in enumerate(futures):
            np.testing.assert_array_equal(
                f.result(timeout=60),
                _ref(params, [[1 + i, 2, 3]], short - 3),
            )
        first = seen[0]
        assert first["live_slots"] >= 3 * S, first
        assert first["queue_depth"] == 0, "a short request was parked"
        assert first["kv_blocks_total"] == S * max_pages
        led = eng.ledger()
        assert led["drained"] and led["balanced"], led
    finally:
        gate.set()
        eng.close()


def test_eight_requests_over_one_prefix_share_seven_times(params):
    """One shared page-aligned prefix under eight different suffixes:
    the first request prefills it, each of the other seven maps its page
    read-only and skips that page's prefill — seven hits, seven whole
    pages of prompt never recomputed, every answer its reference's."""
    common = [3, 5, 2, 9, 11, 4, 7, 1]  # one 8-token page
    eng = _paged_engine(params)
    try:
        for i in range(8):
            p = common + [12 + i, 1 + i]
            got = eng.submit(np.array([p]), 3)
            np.testing.assert_array_equal(got, _ref(params, [p], 3))
        stats = eng.stats()
        assert stats["prefix_hits"] >= 7, stats
        assert stats["prefix_misses"] == 1, stats
        assert stats["prefix_tokens_saved"] >= 7 * stats["block_size"]
    finally:
        eng.close()


# ── live DeviceBudget re-partitioning (PR-7 follow-up) ───────────────────


def test_block_pool_retire_takes_only_free_blocks():
    pool = BlockPool(9)  # 8 usable
    held = pool.alloc(3)
    assert pool.retire(100) == 5  # only the free ones move
    assert pool.usable == 3
    assert pool.free_count() == 0
    # retired blocks are poisoned: naming one is a refcount bug
    with pytest.raises(RuntimeError):
        pool.incref([8])
    # live blocks are untouched and still release cleanly
    pool.release(held)
    assert pool.free_count() == 3


def test_engine_shrink_reclaims_free_then_cached_never_live(params):
    eng = _paged_engine(params, num_blocks=17)  # 16 usable, block=8
    try:
        # a completed request leaves its full prompt pages in the
        # prefix cache (cache-only refs: reclaimable)
        prompt = np.arange(1, 18, dtype=np.int32)[None, :]  # 17 toks
        eng.submit(prompt, 2)
        stats = eng.stats()
        assert stats["kv_blocks_cached"] == 2
        free_before = stats["kv_blocks_free"]  # 14
        # ask for one MORE than free alone: an idle cached page must
        # be evicted and given back too
        assert eng.shrink_blocks(free_before + 1) == free_before + 1
        stats = eng.stats()
        assert stats["kv_blocks_total"] == 1
        assert stats["kv_blocks_cached"] == 1
        assert stats["kv_blocks_retired"] == free_before + 1
        # the shrunken engine still serves (evicting the last cached
        # page under pressure), bit-identically
        got = eng.submit(np.array([[3, 5, 2]]), 4)
        np.testing.assert_array_equal(got, _ref(params, [[3, 5, 2]], 4))
    finally:
        eng.close()


def test_engine_shrink_cannot_touch_live_requests(params):
    eng = _paged_engine(params, num_blocks=5)  # 4 usable
    try:
        # park a slow request so its pages stay live
        fut = eng.enqueue(np.array([[1, 2, 3, 4, 5, 6, 7]]), 9)  # 2 pages
        import time as _t

        deadline = _t.monotonic() + 10
        while eng.stats()["kv_blocks_free"] == 4:
            assert _t.monotonic() < deadline
            _t.sleep(0.005)
        shrunk = eng.shrink_blocks(100)
        # only the blocks NOT held by the live request retired
        assert shrunk <= 2
        assert fut.result(timeout=60).shape == (1, 9)
    finally:
        eng.close()


def test_manager_repartitions_live_engines_on_late_registration(params):
    """The PR-7 'min(share, remaining) forever' pathology closed: when
    model B registers late against one PYGRID_KV_BUDGET, model A's
    engine gives its RECLAIMABLE (free + idle-cached) blocks back and
    B's grant is its true fair share, not the leftovers."""
    from pygrid_tpu.datacentric.model_storage import HostedModel
    from pygrid_tpu.serving import ServingManager

    per_block = pagedkv.block_bytes(CFG, 16, jnp.float32)
    budget = DeviceBudget(total_bytes=16 * per_block)
    mgr = ServingManager(
        EngineConfig(
            max_slots=2, slot_buckets=(1, 2), min_prompt_bucket=8,
            block_size=16, cache_dtype=jnp.float32,
        ),
        budget=budget,
    )
    try:
        hosted_a = HostedModel("model-a", decode.bundle(CFG, params))
        eng_a = mgr.engine_for("model-a", hosted_a)
        # alone, A holds the whole budget (16 blocks incl. trash)
        assert eng_a.stats()["kv_blocks_total"] == 15
        hosted_b = HostedModel("model-b", decode.bundle(CFG, params))
        eng_b = mgr.engine_for("model-b", hosted_b)
        # B's registration repartitioned A down to its fair half —
        # live, without failing anything — and B got a real half,
        # not min(share, nothing-left)
        assert eng_a.stats()["kv_blocks_total"] == 7
        assert eng_b.stats()["kv_blocks_total"] == 7
        # both models still serve bit-identically after the shuffle
        for eng in (eng_a, eng_b):
            got = eng.submit(np.array([[3, 5, 2]]), 4)
            np.testing.assert_array_equal(
                got, _ref(params, [[3, 5, 2]], 4)
            )
    finally:
        mgr.close()


def test_budget_overage_and_record_shrink_ledger():
    budget = DeviceBudget(total_bytes=1000, weights={"a": 1.0, "b": 1.0})
    assert budget.blocks_for("a", 10) == 50  # a's half
    # a is AT its share with b declared: no overage even before b runs
    assert budget.overage("a") == 0
    budget2 = DeviceBudget(total_bytes=1000)
    assert budget2.blocks_for("a", 10) == 100  # alone: everything
    # b joining halves a's fair share → 500 bytes over
    assert budget2.overage("a", joining="b") == 500
    budget2.record_shrink("a", 500)
    assert budget2.overage("a", joining="b") == 0
    # the freed bytes are grantable to b now
    assert budget2.blocks_for("b", 10) == 50


def test_shrink_realized_in_bytes_at_failure_recovery(params):
    """shrink_blocks is logical (admission capacity) until the next
    cache reallocation; a failure recovery must rebuild the device
    arrays at the SHRUNKEN size — otherwise a budget give-back never
    frees real HBM and the node runs over budget indefinitely."""
    eng = _paged_engine(params, num_blocks=17)  # 16 usable
    try:
        assert eng.shrink_blocks(6) == 6
        assert eng.stats()["kv_blocks_total"] == 10
        original = eng.programs.paged_prefill

        def boom(bucket):
            raise RuntimeError("injected device failure")

        eng.programs.paged_prefill = boom
        with pytest.raises(E.PyGridError, match="engine error"):
            eng.submit(np.array([[1, 2]]), 2, timeout=30)
        eng.programs.paged_prefill = original
        stats = eng.stats()
        assert stats["kv_blocks_total"] == 10
        # realized: the pool no longer carries retired placeholders...
        assert stats["kv_blocks_retired"] == 0
        # ...because the arrays themselves are smaller now (10 + trash)
        assert eng._k.shape[1] == 11
        got = eng.submit(np.array([[3, 5, 2]]), 4)
        np.testing.assert_array_equal(got, _ref(params, [[3, 5, 2]], 4))
    finally:
        eng.close()
