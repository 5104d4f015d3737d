"""Bucketed jitted programs for the continuous-batching engine.

The recompile pathology this kills: the legacy per-request path jits one
whole-generation program per distinct ``n_new`` (and jax retraces again
per prompt length), so a serving node facing organic traffic compiles
constantly. Here the compiled surface is fixed up front:

- one **prefill** program per prompt-length *bucket* (prompt padded up,
  true length traced) — admission cost is O(#buckets) compiles ever;
- one **decode-step** program per slot-width *bucket* — the steady-state
  loop is O(#width buckets) compiles ever;
- ``n_new`` never appears in any trace: it is a host-side loop bound.

Temperature and the PRNG key are traced arguments (the greedy/sampled
choice is a ``jnp.where`` inside the program), so request sampling
parameters cannot force a retrace either. Every compile increments the
``serving_compiles_total`` counter — the bench and tests assert the
count stays flat while request shapes vary within buckets.

Cache buffers are donated (``donate_argnums``): the engine owns the only
reference, so XLA may update the multi-megabyte k/v arrays in place
instead of copying them every step.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

from pygrid_tpu import telemetry


def prompt_buckets(max_len: int, smallest: int = 16) -> tuple[int, ...]:
    """Doubling ladder of prompt pad widths, capped at ``max_len``:
    16, 32, … max_len. A request's prompt pads up to the first bucket
    that fits, so at most log2(max_len/16)+1 prefill programs exist."""
    buckets: list[int] = []
    b = min(smallest, max_len)
    while b < max_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_len)
    return tuple(buckets)


def width_buckets(max_slots: int, ladder: Sequence[int]) -> tuple[int, ...]:
    """Slot-width buckets ≤ ``max_slots`` (always including it), so the
    decode program runs at the narrowest width covering the live slots."""
    widths = sorted({w for w in ladder if 0 < w < max_slots} | {max_slots})
    return tuple(widths)


class ProgramSet:
    """The jitted-program cache for one hosted model: keyed only by
    bucket sizes, never by request shape. ``compile_count()`` is the
    observable the no-recompile contract is asserted against."""

    def __init__(
        self,
        cfg,
        compute_dtype: Any | None = None,
        cache_dtype: Any | None = None,
        model_id: str = "",
        draft_cfg: Any | None = None,
    ) -> None:
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.cache_dtype = cache_dtype
        self.model_id = model_id
        #: truncated-layer draft config for the speculative programs
        #: (None: spec_prefill/spec_verify are unavailable)
        self.draft_cfg = draft_cfg
        from pygrid_tpu.models import decode

        #: the module that serves this config's family: the paged
        #: programs reach the model only through it
        self._family = decode.family_of(cfg)
        #: its paged cache: ``k, v, pos`` and whatever state the family
        #: keeps beside them, all donated (argument 0 is ``params``)
        self._cache_of = self._family.PagedCache._make
        self._cache_arrays = len(self._family.PagedCache._fields)
        self._donated = tuple(range(1, 1 + self._cache_arrays))
        self._prefill: dict[int, Callable] = {}
        self._decode: dict[int, Callable] = {}
        self._paged_prefill: dict[int, Callable] = {}
        self._paged_decode: dict[int, Callable] = {}
        self._paged_fused: dict[tuple[int, int], Callable] = {}
        self._spec_prefill: dict[int, Callable] = {}
        self._spec_verify: dict[tuple[int, int], Callable] = {}
        self._compiles = 0

    def compile_count(self) -> int:
        return self._compiles

    def trace_count(self) -> int:
        """Actual jit cache entries across every program — catches
        silent retraces (shape/dtype drift in engine call sites) that
        the builder-level counter cannot see. Equals
        :meth:`compile_count` when the no-recompile contract holds."""
        return sum(
            fn._cache_size()
            for fn in [
                *self._prefill.values(),
                *self._decode.values(),
                *self._paged_prefill.values(),
                *self._paged_decode.values(),
                *self._paged_fused.values(),
                *self._spec_prefill.values(),
                *self._spec_verify.values(),
            ]
        )

    def _count(self, kind: str) -> None:
        self._compiles += 1
        telemetry.incr("serving_compiles_total", kind=kind)

    @staticmethod
    def _pick(logits, temp, key):
        """Greedy/sampled token from one [vocab] logits row; ``temp`` is
        traced so one program serves every temperature INCLUDING zero
        (the jnp.where guard — categorical over logits/0 is NaN)."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("sample"):
            safe_t = jnp.where(temp > 0.0, temp, jnp.float32(1.0))
            sampled = jax.random.categorical(key, logits / safe_t, axis=-1)
            return jnp.where(
                temp > 0.0, sampled, jnp.argmax(logits, axis=-1)
            ).astype(jnp.int32)

    def prefill(self, bucket: int) -> Callable:
        """``fn(params, k, v, pos, slot, prompt[bucket], length, temp,
        key) -> (first_token, k, v, pos)`` — admission of one request
        into one slot, first token picked on-device."""
        fn = self._prefill.get(bucket)
        if fn is None:
            import jax

            from pygrid_tpu.models import decode

            cfg, cd = self.cfg, self.compute_dtype

            def _prefill(params, k, v, pos, slot, prompt, length, temp, key):
                cache = decode.SlotKVCache(k=k, v=v, pos=pos)
                logits, cache = decode.prefill_slot(
                    params, cache, slot, prompt, length, cfg, cd
                )
                tok = self._pick(logits, temp, key)
                return tok, cache.k, cache.v, cache.pos

            fn = telemetry.profiler.wrap(
                jax.jit(_prefill, donate_argnums=(1, 2, 3)),
                kind="prefill", bucket=bucket, model_id=self.model_id,
            )
            self._prefill[bucket] = fn
            self._count("prefill")
        return fn

    def decode(self, width: int) -> Callable:
        """``fn(params, k, v, pos, tokens[w], temps[w], keys[w, 2]) ->
        (next_tokens[w], k, v, pos)`` — one step for the first ``w``
        slots, each at its own position, next token picked on-device per
        slot with that slot's temperature/key."""
        fn = self._decode.get(width)
        if fn is None:
            import jax

            from pygrid_tpu.models import decode

            cfg, cd = self.cfg, self.compute_dtype

            def _decode_step(params, k, v, pos, tokens, temps, keys):
                cache = decode.SlotKVCache(k=k, v=v, pos=pos)
                logits, cache = decode.decode_step_slots(
                    params, cache, tokens, cfg, cd
                )
                toks = jax.vmap(self._pick)(logits, temps, keys)
                return toks, cache.k, cache.v, cache.pos

            fn = telemetry.profiler.wrap(
                jax.jit(_decode_step, donate_argnums=(1, 2, 3)),
                kind="decode", bucket=width, model_id=self.model_id,
            )
            self._decode[width] = fn
            self._count("decode")
        return fn

    # ── paged (block-table) programs ────────────────────────────────────
    #
    # Same bucketing contract as the contiguous pair above: one compile
    # per chunk/width bucket ever, with the block TABLE a plain traced
    # argument (constant [S, max_pages] shape — table content changes at
    # admission without retracing) and ``start``/``length`` traced so a
    # prefix hit of any block-aligned depth reuses one program.

    def paged_prefill(self, bucket: int) -> Callable:
        """``fn(params, k, v, pos, table, slot, chunk[bucket], start,
        length, temp, key) -> (first_token, k, v, pos)`` — admission of
        one request through its block table, continuing after a shared
        prefix of ``start`` tokens; first token picked on-device. A
        family whose cache has more arrays than ``k, v, pos`` (a
        recurrent state) takes and returns them after ``pos``, donated
        like the rest: so for every paged program below."""
        fn = self._paged_prefill.get(bucket)
        if fn is None:
            import jax

            model, cfg, cd = self._family, self.cfg, self.compute_dtype
            cache_of, n = self._cache_of, self._cache_arrays

            def _paged_prefill(params, *args):
                table, slot, chunk, start, length, temp, key = args[n:]
                logits, cache = model.paged_prefill_chunk(
                    params, cache_of(args[:n]), table, slot, chunk, start,
                    length, cfg, cd,
                )
                tok = self._pick(logits, temp, key)
                return (tok, *cache)

            fn = telemetry.profiler.wrap(
                jax.jit(_paged_prefill, donate_argnums=self._donated),
                kind="paged_prefill", bucket=bucket,
                model_id=self.model_id,
            )
            self._paged_prefill[bucket] = fn
            self._count("paged_prefill")
        return fn

    def paged_decode_fused(self, width: int, steps: int) -> Callable:
        """``fn(params, k, v, pos, table, tokens[w], budget[w],
        temps[w], keys[steps, w, 2]) -> (emitted[steps, w], k, v, pos)``
        — up to ``steps`` block-table decode steps in ONE compiled
        program (``lax.scan``), killing the per-step host→device
        dispatch that dominates small-model decode.

        ``budget[i]`` is how many tokens row ``i`` still needs: the scan
        decrements it per step and FREEZES the row at zero (k/v write to
        trash, position parked, token carried — see
        ``decode.paged_decode_step``'s ``active`` mask), so rows that
        finish mid-scan cost wasted FLOPs but zero state damage. The
        emitted [steps, w] matrix holds every step's token; the engine
        drains the first ``budget`` entries per row and ignores the
        frozen tail. ``steps`` is static (the engine's quantum — the
        fairness cap between admission checks), so the compiled surface
        stays one program per (width, quantum)."""
        cache_key = (width, steps)
        fn = self._paged_fused.get(cache_key)
        if fn is None:
            import jax
            import jax.numpy as jnp
            from jax import lax

            model, cfg, cd = self._family, self.cfg, self.compute_dtype
            cache_of, n = self._cache_of, self._cache_arrays

            def _fused(params, *args):
                table, tokens, budget, temps, keys = args[n:]

                def body(carry, step_keys):
                    *arrays, tok, remaining = carry
                    alive = remaining > 0
                    logits, cache = model.paged_decode_step(
                        params, cache_of(arrays), table, tok, cfg, cd,
                        active=alive,
                    )
                    picked = jax.vmap(self._pick)(logits, temps, step_keys)
                    nxt = jnp.where(alive, picked, tok)
                    carry = (
                        *cache, nxt, remaining - alive.astype(jnp.int32),
                    )
                    return carry, nxt

                (*arrays, _, _), emitted = lax.scan(
                    body, (*args[:n], tokens, budget), keys
                )
                return (emitted, *arrays)

            fn = telemetry.profiler.wrap(
                jax.jit(_fused, donate_argnums=self._donated),
                kind="paged_decode_fused", bucket=width,
                model_id=self.model_id,
            )
            self._paged_fused[cache_key] = fn
            self._count("paged_decode_fused")
        return fn

    def paged_decode(self, width: int) -> Callable:
        """``fn(params, k, v, pos, table, tokens[w], temps[w],
        keys[w, 2]) -> (next_tokens[w], k, v, pos)`` — one block-table
        step for the first ``w`` slots, each at its own position."""
        fn = self._paged_decode.get(width)
        if fn is None:
            import jax

            model, cfg, cd = self._family, self.cfg, self.compute_dtype
            cache_of, n = self._cache_of, self._cache_arrays

            def _paged_decode_step(params, *args):
                table, tokens, temps, keys = args[n:]
                logits, cache = model.paged_decode_step(
                    params, cache_of(args[:n]), table, tokens, cfg, cd
                )
                toks = jax.vmap(self._pick)(logits, temps, keys)
                return (toks, *cache)

            fn = telemetry.profiler.wrap(
                jax.jit(_paged_decode_step, donate_argnums=self._donated),
                kind="paged_decode", bucket=width,
                model_id=self.model_id,
            )
            self._paged_decode[width] = fn
            self._count("paged_decode")
        return fn

    # ── self-speculative programs (truncated-layer draft) ───────────────
    #
    # The draft shares the paged pool's BLOCK IDS: its k/v arrays carry
    # fewer layers but use the same tables, so every allocation /
    # prefix-share / COW rule covers both caches with zero extra
    # bookkeeping. Both programs donate every cache buffer and keep the
    # table/start/length traced — same no-recompile contract as the
    # non-speculative set.

    def spec_prefill(self, bucket: int) -> Callable:
        """``fn(params, dparams, k, v, pos, dk, dv, table, slot,
        chunk[bucket], start, length, temp, key) -> (first_token, k, v,
        pos, dk, dv)`` — admission when spec decode is on: one program
        prefills the chunk through BOTH caches (the draft needs the
        prompt's k/v before it can propose), first token picked from the
        TARGET logits, so admission output is bit-identical to the
        non-speculative path."""
        fn = self._spec_prefill.get(bucket)
        if fn is None:
            import jax

            from pygrid_tpu.models import decode

            cfg, dcfg, cd = self.cfg, self.draft_cfg, self.compute_dtype

            def _spec_prefill(
                params, dparams, k, v, pos, dk, dv, table, slot, chunk,
                start, length, temp, key,
            ):
                cache = decode.PagedKVCache(k=k, v=v, pos=pos)
                logits, cache = decode.paged_prefill_chunk(
                    params, cache, table, slot, chunk, start, length,
                    cfg, cd,
                )
                dcache = decode.PagedKVCache(k=dk, v=dv, pos=pos)
                # draft logits are dead code (XLA DCEs the draft's
                # output head) — this pass exists only to write the
                # draft's k/v rows for the prompt
                _dl, dcache = decode.paged_prefill_chunk(
                    dparams, dcache, table, slot, chunk, start, length,
                    dcfg, cd,
                )
                tok = self._pick(logits, temp, key)
                return tok, cache.k, cache.v, cache.pos, dcache.k, dcache.v

            fn = telemetry.profiler.wrap(
                jax.jit(_spec_prefill, donate_argnums=(2, 3, 4, 5, 6)),
                kind="spec_prefill", bucket=bucket,
                model_id=self.model_id,
            )
            self._spec_prefill[bucket] = fn
            self._count("spec_prefill")
        return fn

    def spec_verify(self, width: int, k_spec: int) -> Callable:
        """``fn(params, dparams, k, v, pos, dk, dv, table, tokens[w],
        active[w], temps[w], keys[w, K, 2]) -> (emitted[w, K],
        accepted[w], counts[w], k, v, pos, dk, dv)`` — one speculative
        decode cycle for the first ``w`` slots in ONE compiled program:

        1. the DRAFT proposes K tokens autoregressively (a ``lax.scan``
           of truncated-layer block-table steps — cheap, and fused so
           the chain costs one dispatch, not K);
        2. the TARGET verifies all K in one wide step through the block
           tables (``decode.paged_verify_chunk`` — prefill-style
           arithmetic intensity);
        3. acceptance picks the emitted run: greedy rows accept while
           the proposal equals the target argmax and emit the target's
           token at the first mismatch — BIT-IDENTICAL to plain greedy
           decode by construction; sampling rows accept proposal ``x``
           with probability ``min(1, p_t(x)/p_d(x))`` and sample the
           first rejection from ``norm(max(p_t - p_d, 0))`` — the
           standard speculative-sampling estimator (target-distribution
           exact), with every random draw keyed from the row's
           per-position key schedule (``fold_in`` tags 1/2/3 for
           draft/accept/residual), so output is reproducible per
           (seed, row).

        ``counts[i]`` ∈ [1, K] tokens emitted per active row (0 for
        frozen rows); ``accepted[i]`` is the count of ACCEPTED draft
        proposals — the honest acceptance-rate numerator (``counts``
        includes the free correction token)."""
        cache_key = (width, k_spec)
        fn = self._spec_verify.get(cache_key)
        if fn is None:
            import jax
            import jax.numpy as jnp
            from jax import lax

            from pygrid_tpu.models import decode

            cfg, dcfg, cd = self.cfg, self.draft_cfg, self.compute_dtype

            def _spec_verify(
                params, dparams, k, v, pos, dk, dv, table, tokens,
                active, temps, keys,
            ):
                keys_t = jnp.transpose(keys, (1, 0, 2))  # [K, w, 2]

                def dbody(carry, step_keys):
                    dkk, dvv, dpp, tok = carry
                    dcache = decode.PagedKVCache(k=dkk, v=dvv, pos=dpp)
                    dlogits, dcache = decode.paged_decode_step(
                        dparams, dcache, table, tok, dcfg, cd,
                        active=active,
                    )
                    draft_keys = jax.vmap(
                        lambda kk: jax.random.fold_in(kk, 1)
                    )(step_keys)
                    proposal = jax.vmap(self._pick)(
                        dlogits, temps, draft_keys
                    )
                    carry = (dcache.k, dcache.v, dcache.pos, proposal)
                    return carry, (tok, proposal, dlogits)

                (dkk, dvv, _dpp, _), (fed, props, dlg) = lax.scan(
                    dbody, (dk, dv, pos, tokens), keys_t
                )
                cache = decode.PagedKVCache(k=k, v=v, pos=pos)
                tlogits, cache = decode.paged_verify_chunk(
                    params, cache, table, fed.T, cfg, cd, active=active
                )  # [w, K, vocab]
                X = props.T  # [w, K] proposal for emitted index j
                D = jnp.transpose(dlg, (1, 0, 2))  # [w, K, vocab]
                greedy_tok = jnp.argmax(tlogits, axis=-1).astype(
                    jnp.int32
                )  # [w, K]
                safe_t = jnp.where(temps > 0.0, temps, jnp.float32(1.0))
                p_t = jax.nn.softmax(tlogits / safe_t[:, None, None], -1)
                p_d = jax.nn.softmax(D / safe_t[:, None, None], -1)
                px_t = jnp.take_along_axis(p_t, X[:, :, None], -1)[..., 0]
                px_d = jnp.take_along_axis(p_d, X[:, :, None], -1)[..., 0]

                def fold2(tag):
                    return jax.vmap(
                        jax.vmap(lambda kk: jax.random.fold_in(kk, tag))
                    )(keys)

                u = jax.vmap(jax.vmap(jax.random.uniform))(fold2(2))
                # u ≤ p_t/p_d, multiplied through: a zero draft prob
                # (can't be sampled, but denormals happen) accepts
                sampled_ok = u * px_d <= px_t
                greedy_ok = X == greedy_tok
                ok = jnp.where(
                    temps[:, None] > 0.0, sampled_ok, greedy_ok
                )
                lead = jnp.cumprod(ok.astype(jnp.int32), axis=1)
                n_acc = lead.sum(axis=1)  # [w] accepted proposals
                residual = jnp.clip(p_t - p_d, 0.0, None)
                resid_tok = jax.vmap(
                    jax.vmap(
                        lambda kk, lg: jax.random.categorical(kk, lg)
                    )
                )(fold2(3), jnp.log(residual + 1e-20)).astype(jnp.int32)
                corr = jnp.where(
                    temps[:, None] > 0.0, resid_tok, greedy_tok
                )
                jidx = jnp.arange(X.shape[1])[None, :]
                emitted = jnp.where(
                    jidx < n_acc[:, None], X,
                    jnp.where(jidx == n_acc[:, None], corr, 0),
                )
                counts = jnp.minimum(n_acc + 1, X.shape[1]).astype(
                    jnp.int32
                )
                counts = jnp.where(active, counts, 0)
                new_pos = cache.pos.at[: counts.shape[0]].add(counts)
                return (
                    emitted, n_acc.astype(jnp.int32), counts,
                    cache.k, cache.v, new_pos, dkk, dvv,
                )

            fn = telemetry.profiler.wrap(
                jax.jit(_spec_verify, donate_argnums=(2, 3, 4, 5, 6)),
                kind="spec_verify", bucket=width,
                model_id=self.model_id,
            )
            self._spec_verify[cache_key] = fn
            self._count("spec_verify")
        return fn
