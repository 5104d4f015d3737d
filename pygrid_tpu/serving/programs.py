"""Bucketed jitted programs for the continuous-batching engine.

A program per distinct ``n_new`` or prompt length would have a serving
node facing organic traffic compile constantly. Here the compiled
surface is fixed up front:

- one **prefill** program per prompt-length *bucket* (prompt padded up,
  true length traced) — admission cost is O(#buckets) compiles ever;
- one **decode-step** program per slot-width *bucket*, and one
  **fused** ``quantum``-step scan per width — the steady-state loop is
  O(#width buckets) compiles ever; a family whose forward carries a
  block of positions (``BLOCK_LEN`` > 1) has one **block-step** program
  per width in their place;
- ``n_new`` never appears in any trace: it is a host-side loop bound.

Temperature and the PRNG key are traced arguments (the greedy/sampled
choice is a ``jnp.where`` inside the program), so request sampling
parameters cannot force a retrace either. Every compile increments the
``serving_compiles_total`` counter — the tests assert the count stays
flat while request shapes vary within buckets.

Cache buffers are donated (``donate_argnums``): the engine owns the only
reference, so XLA may update the multi-megabyte k/v arrays in place
instead of copying them every step.

A causal family's LAST TOKENS live on the device: one int32 vector over
the slots (``last``) rides after the cache arrays, donated like them. A
prefill writes the admitted slot's first token into it, a decode step
reads its first ``w`` entries as its input and writes the picked tokens
back, a fused scan starts from it and leaves its carry there. So a
step's input never waits for the host to have fetched the step before
it; what a program answers the host (``tok``, ``toks``, ``emitted``) is
an array of its own, which the next program does not consume.

A block family's CURRENT BLOCK lives there in ``last``'s place, a pair
over the slots: the block's tokens (int32 ``[slots, BLOCK_LEN]``) and
which of its positions are still masked (bool, the same shape). A
prefill opens the admitted slot's block (every position masked); a block
step reads the first ``w`` rows, takes in what the host knows of a row's
first block (the prompt's tail), runs the forward and applies the reveal
there. The forward after the one that made a block whole carries that
block beside the next: its tokens are what ``last`` holds then, so the
commit of a block needs no forward of its own and nothing from the host
(the next block opens in the same program, every position masked). So a
forward's input never waits for the host to have fetched the forward
before it either; ``tokens`` and ``chosen`` are what the host reads, a
dispatch late.
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
    ) -> None:
        self.cfg = cfg
        self.compute_dtype = compute_dtype
        self.cache_dtype = cache_dtype
        self.model_id = model_id
        from pygrid_tpu.models import decode

        #: the module that serves this config's family: the paged
        #: programs reach the model only through it
        self._family = decode.family_of(cfg)
        #: its paged cache: ``k, v, pos`` and whatever state the family
        #: keeps beside them, all donated (argument 0 is ``params``)
        self._cache_of = self._family.PagedCache._make
        #: positions a row's forward carries (1: ``last`` is a token a
        #: slot; more: the pair that holds a slot's block)
        self._block_len = int(self._family.BLOCK_LEN)
        self._cache_arrays = len(self._family.PagedCache._fields)
        #: every program carries ``last`` (or the block) after them and
        #: donates it too
        self._donated = tuple(range(1, 2 + self._cache_arrays))
        self._paged_prefill: dict[int, Callable] = {}
        self._paged_decode: dict[int, Callable] = {}
        self._paged_fused: dict[tuple[int, int], Callable] = {}
        self._paged_block: dict[int, Callable] = {}
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
                *self._paged_prefill.values(),
                *self._paged_decode.values(),
                *self._paged_fused.values(),
                *self._paged_block.values(),
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

    @staticmethod
    def _reveal(logits, masked, n_reveal):
        """What one forward of a block reveals. ``logits`` [w, L, vocab]:
        each position's own; ``masked`` [w, L] bool; ``n_reveal`` [w]:
        how many of a row's masked positions to reveal (0: none, a free
        slot). A position's token is its argmax and its confidence the
        largest probability of its softmax; the ``n_reveal`` most
        confident masked positions are chosen, the earlier at a tie.
        Returns (tokens [w, L] int32, chosen [w, L] bool)."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("sample.reveal"):
            top = jnp.max(logits, axis=-1)
            conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
            conf = jnp.where(masked, conf, -1.0)
            at = jnp.arange(conf.shape[-1])
            ahead = (conf[:, None, :] > conf[:, :, None]) | (
                (conf[:, None, :] == conf[:, :, None])
                & (at[None, None, :] < at[None, :, None])
            )
            chosen = masked & (ahead.sum(-1) < n_reveal[:, None])
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), chosen

    # One compile per chunk/width bucket ever, with the block TABLE a
    # plain traced argument (constant [S, max_pages] shape — table
    # content changes at admission without retracing) and ``start``/
    # ``length`` traced so a prefix hit of any block-aligned depth reuses
    # one program.

    def paged_prefill(self, bucket: int) -> Callable:
        """``fn(params, k, v, pos, last, table, slot, chunk[bucket],
        start, length, temp, key) -> (first_token, k, v, pos, last)`` —
        admission of one request through its block table, continuing
        after a shared prefix of ``start`` tokens; first token picked
        on-device and left in ``last[slot]`` (a block family's prefill
        yields no token: it opens the slot's block, every position
        masked). A family whose cache has
        more arrays than ``k, v, pos`` (a recurrent state) takes and
        returns them after ``pos``, donated like the rest: so for every
        paged program below. What a family's prefill or step answers
        after its cache (what it counted of its experts) comes back
        between the tokens and the cache: so for every paged program
        below too (a scan answers the sum over its steps)."""
        fn = self._paged_prefill.get(bucket)
        if fn is None:
            import jax

            model, cfg, cd = self._family, self.cfg, self.compute_dtype
            cache_of, n = self._cache_of, self._cache_arrays

            def _paged_prefill(params, *args):
                last, table, slot, chunk, start, length, temp, key = args[n:]
                logits, cache, *counted = model.paged_prefill_chunk(
                    params, cache_of(args[:n]), table, slot, chunk, start,
                    length, cfg, cd,
                )
                tok = self._pick(logits, temp, key)
                if self._block_len > 1:
                    tokens, masked = last
                    last = tokens.at[slot].set(0), masked.at[slot].set(True)
                else:
                    last = last.at[slot].set(tok)
                return (tok, *counted, *cache, last)

            fn = telemetry.profiler.wrap(
                jax.jit(_paged_prefill, donate_argnums=self._donated),
                kind="paged_prefill", bucket=bucket,
                model_id=self.model_id,
            )
            self._paged_prefill[bucket] = fn
            self._count("paged_prefill")
        return fn

    def paged_decode_fused(self, width: int, steps: int) -> Callable:
        """``fn(params, k, v, pos, last, table, budget[w], temps[w],
        keys[steps, w, 2]) -> (emitted[steps, w], k, v, pos, last)`` —
        up to ``steps`` block-table decode steps in ONE compiled
        program (``lax.scan``), killing the per-step host→device
        dispatch that dominates small-model decode. The scan starts from
        ``last[:w]`` and leaves its carry there.

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
                last, table, budget, temps, keys = args[n:]
                width = budget.shape[0]

                def body(carry, step_keys):
                    *arrays, tok, remaining = carry
                    alive = remaining > 0
                    logits, cache, *counted = model.paged_decode_step(
                        params, cache_of(arrays), table, tok, cfg, cd,
                        active=alive,
                    )
                    picked = jax.vmap(self._pick)(logits, temps, step_keys)
                    nxt = jnp.where(alive, picked, tok)
                    carry = (
                        *cache, nxt, remaining - alive.astype(jnp.int32),
                    )
                    return carry, (nxt, *counted)

                (*arrays, tok, _), (emitted, *counted) = lax.scan(
                    body, (*args[:n], last[:width], budget), keys
                )
                return (
                    emitted, *(c.sum(0) for c in counted), *arrays,
                    last.at[:width].set(tok),
                )

            fn = telemetry.profiler.wrap(
                jax.jit(_fused, donate_argnums=self._donated),
                kind="paged_decode_fused", bucket=width,
                model_id=self.model_id,
            )
            self._paged_fused[cache_key] = fn
            self._count("paged_decode_fused")
        return fn

    def paged_decode(self, width: int) -> Callable:
        """``fn(params, k, v, pos, last, table, temps[w], keys[w, 2]) ->
        (next_tokens[w], k, v, pos, last)`` — one block-table step for
        the first ``w`` slots, each at its own position, from the token
        ``last`` holds for it to the one it leaves there."""
        fn = self._paged_decode.get(width)
        if fn is None:
            import jax

            model, cfg, cd = self._family, self.cfg, self.compute_dtype
            cache_of, n = self._cache_of, self._cache_arrays

            def _paged_decode_step(params, *args):
                last, table, temps, keys = args[n:]
                width = temps.shape[0]
                logits, cache, *counted = model.paged_decode_step(
                    params, cache_of(args[:n]), table, last[:width], cfg, cd
                )
                toks = jax.vmap(self._pick)(logits, temps, keys)
                return (toks, *counted, *cache, last.at[:width].set(toks))

            fn = telemetry.profiler.wrap(
                jax.jit(_paged_decode_step, donate_argnums=self._donated),
                kind="paged_decode", bucket=width,
                model_id=self.model_id,
            )
            self._paged_decode[width] = fn
            self._count("paged_decode")
        return fn

    def paged_block_step(self, width: int) -> Callable:
        """``fn(params, k, v, pos, block, table, tail[w, L], n_reveal[w],
        commit[w]) -> (tokens[w, L], chosen[w, L], expert_bytes, k, v,
        pos, block)`` — one forward of the current block of the first
        ``w`` slots of a family whose forward carries ``L = BLOCK_LEN``
        positions. ``block`` is the pair ``(tokens[slots, L],
        masked[slots, L])`` the device keeps beside the cache, donated.
        ``tail`` is what the host knows of a block that no forward has
        run yet: a token (>= 0) stands at its position, known, whatever
        the device held there; -1 leaves the position as it is. A row's
        first forward brings the prompt's tail so; a free slot inside the
        width brings four known zeros. Rows in different phases share the
        dispatch: every row reveals ``n_reveal`` of its block's masked
        positions (``chosen``, each with the token beside it in
        ``tokens``; they take those tokens and lose their mask in
        ``block``). A row whose last forward made its block whole says
        ``commit``: what ``block`` holds for it is then the block BEFORE,
        which rides in this forward with its tokens known (its K/V stand,
        the row's position moves on past it), and the block the forward
        denoises is the next one, opened here with every position masked.
        ``expert_bytes`` is what the forward read of the experts'
        weights, as the family counts it."""
        fn = self._paged_block.get(width)
        if fn is None:
            import jax
            import jax.numpy as jnp

            model, cfg, cd = self._family, self.cfg, self.compute_dtype
            cache_of, n = self._cache_of, self._cache_arrays

            def _paged_block_step(params, *args):
                (tokens, masked), table, tail, n_reveal, commit = args[n:]
                width = n_reveal.shape[0]
                known = tail >= 0
                blk = jnp.where(known, tail, tokens[:width])
                hidden = (masked[:width] & ~known) | commit[:, None]
                logits, cache, expert_bytes = model.paged_decode_step(
                    params, cache_of(args[:n]), table, blk, cfg, cd,
                    before=tokens[:width], commit=commit, masked=hidden,
                )
                toks, chosen = self._reveal(logits, hidden, n_reveal)
                return (
                    toks, chosen, expert_bytes, *cache,
                    (
                        tokens.at[:width].set(jnp.where(chosen, toks, blk)),
                        masked.at[:width].set(hidden & ~chosen),
                    ),
                )

            fn = telemetry.profiler.wrap(
                jax.jit(_paged_block_step, donate_argnums=self._donated),
                kind="paged_block_step", bucket=width,
                model_id=self.model_id,
            )
            self._paged_block[width] = fn
            self._count("paged_block_step")
        return fn
