"""A training cell: ``make_fused_rounds`` over the flash-attention
transformer, one dispatch of ``n_rounds`` FedAvg rounds after another for
the window, each waited for with ``block_until_ready`` while the next is
already queued.

Set-up builds ONE object, the jitted rounds function with its state, and
drives it from the seed through its first dispatch; the window gets that
same object and the state it left. The reference (plain float32 SGD on the
same batch) follows that first dispatch round for round after the window,
when the program's state has been freed.
"""

from __future__ import annotations

import functools
import gc
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from lib import peaks, reference
from lib.readers import memory_peak
from lib.trace import CompileCounter


def build_step(cell: dict, interpret: bool):
    """The timed object: ``rounds_fn(params, X, y, lr) -> (params, losses,
    accs)``, ``n_rounds`` fused FedAvg rounds in one program."""
    from pygrid_tpu.models import transformer
    from pygrid_tpu.parallel import make_fused_rounds
    from pygrid_tpu.parallel.pallas_attention import flash_attention

    from lib.serving import transformer_config

    tr, train = cell["traffic"], cell["config"]["deployment"]["training"]
    loss_fn = functools.partial(
        transformer.loss_and_acc,
        cfg=transformer_config(cell["config"]),
        attn_fn=functools.partial(flash_attention, interpret=interpret),
        compute_dtype=train["compute_dtype"],
        ce_chunk=tr["ce_chunk"],
    )
    return make_fused_rounds(
        loss_fn, n_rounds=tr["n_rounds"], local_steps=tr["local_steps"]
    )


def make_batch(cell: dict, seed: int):
    """Client batches ``[clients, batch, seq_len]`` from the seed, on the
    device: every row differs; targets are the inputs shifted by one."""
    import jax
    import jax.numpy as jnp

    tr = cell["traffic"]
    X = jax.random.randint(
        jax.random.fold_in(reference.seed_key(seed), 0xDA7A),
        (tr["clients"], tr["batch"], tr["seq_len"]), 0,
        cell["config"]["vocab_size"],
    )
    return X, jnp.roll(X, -1, axis=-1)


def _delta_norms(new, old, cfg: dict) -> dict:
    import jax

    delta = [a - b for a, b in zip(new, old)]
    return reference.leaf_norms(
        jax.block_until_ready(reference.stack_program_params(delta, cfg))
    )


def check_trained(cell: dict, seed: int, X, y, first_losses, delta_norms,
                  window_losses, control: bool, log=print) -> dict:
    """``correct`` for a training cell. The reference starts from the
    seed's weights and takes the first dispatch's rounds as plain SGD
    steps. Compared: the loss of each of the first three rounds, and the
    norm of the parameters' change over the dispatch by the worst leaf;
    and the loss has to be finite and still falling at the window's end."""
    import jax
    import jax.numpy as jnp

    cfg, tr = cell["config"], cell["traffic"]
    check = tr["check"]
    rows = X.reshape(-1, X.shape[-1])
    targets = y.reshape(-1, y.shape[-1])
    w0 = reference.make_weights(seed, cfg, "float32")
    args = (rows, targets, tr["lr"], cfg, tr["n_rounds"], check["ref_chunk"])
    w_ref, ref_losses = reference.sgd_steps(w0, *args)
    ref_norms = reference.leaf_norms(jax.tree.map(jnp.subtract, w_ref, w0))
    del w_ref

    def numbers(losses, norms):
        out = {
            f"loss_gap_round{i}": abs(losses[i] - ref_losses[i]) / abs(ref_losses[i])
            for i in range(3)
        }
        out["param_change_gap_worst_leaf"] = reference.worst_leaf_gap(norms, ref_norms)
        return out

    got = numbers([float(v) for v in first_losses], delta_norms)
    compared = [
        {"name": k, "value": v,
         "limit": check["loss_gap_limit" if k.startswith("loss") else "param_change_gap_limit"]}
        for k, v in got.items()
    ]
    finite = bool(np.isfinite(window_losses).all() and np.isfinite(first_losses).all())
    # progress from dispatch to dispatch: the window's last dispatch opens
    # below where the first one closed (a step that hands its state back
    # unchanged would open where the first one opened)
    falling = finite and float(window_losses[0]) < float(first_losses[-1])
    compared.append({"name": "loss_not_falling", "value": 0.0 if falling else 1.0, "limit": 0.0})
    out = {
        "correct": finite and all(c["value"] <= c["limit"] for c in compared),
        "compared": compared,
        "reference_losses": ref_losses[:3],
        "program_losses": [float(v) for v in first_losses[:3]],
        "loss_at_window_end": float(window_losses[-1]),
    }
    if control:
        w_c, c_losses = reference.sgd_steps(w0, *args, dtype=jnp.bfloat16)
        c_norms = reference.leaf_norms(
            jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, w_c, w0)
        )
        out["control"] = numbers(c_losses, c_norms)
    return out


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
             control: bool = False, interpret: bool = False, step_hook=None,
             log=print) -> dict:
    """One run of a training cell. ``step_hook`` lets a self-test wrap the
    timed object to break it."""
    import jax
    import jax.numpy as jnp

    cfg, tr = cell["config"], cell["traffic"]
    phases = {"process_and_jax_s": time.time() - t_start}
    compiles = CompileCounter()

    t = time.time()
    dtype = cell["config"]["deployment"]["training"]["params_dtype"]
    params = reference.make_program_params(seed, cfg, dtype)
    X, y = make_batch(cell, seed)
    jax.block_until_ready((params, X, y))
    phases["weights_and_batch_s"] = time.time() - t

    t = time.time()
    rounds_fn = build_step(cell, interpret)
    if step_hook is not None:
        rounds_fn = step_hook(rounds_fn)
    lr = jnp.float32(tr["lr"])
    # the first dispatch, from the seed: compiles (or loads), runs, and is
    # what the reference follows
    state, first_losses, _ = jax.block_until_ready(rounds_fn(params, X, y, lr))
    phases["first_dispatch_s"] = time.time() - t
    t = time.time()
    delta_norms = _delta_norms(state, params, cfg)
    first_losses = np.asarray(first_losses)
    del params
    phases["first_dispatch_norms_s"] = time.time() - t

    workdir = Path(tempfile.mkdtemp(prefix="perfbench_"))
    tracer = None
    if trace:
        from lib.trace import Tracer

        tracer = Tracer(workdir / "trace")
    compiled_before = compiles.n
    setup_s = time.time() - t_start
    t0 = time.perf_counter()
    # one dispatch is always queued behind the one that runs, as a caller
    # who does not wait between calls would have it: a stall of the host
    # between two dispatches (seen: 0.7 and 1.2 s, PR 23) then idles nothing
    dispatches, ends = 0, [0.0]
    if tracer is not None:
        tracer.start()
    running = rounds_fn(state, X, y, lr)
    del state
    while True:
        queued = rounds_fn(running[0], X, y, lr)
        jax.block_until_ready(running[0])
        dispatches += 1
        now = time.perf_counter() - t0
        ends.append(now)
        running = queued
        if tracer is not None and tracer.window_s == 0.0 and (
            now >= tr["trace_s"] or now >= seconds
        ):
            tracer.stop()
        if now >= seconds:
            break
    # the queued one is work of the window too: its rounds and its time count
    state, losses, _ = jax.block_until_ready(running)
    dispatches += 1
    ends.append(time.perf_counter() - t0)
    elapsed = time.perf_counter() - t0
    compiled_in_window = compiles.n - compiled_before
    window_losses = np.asarray(losses)
    mem = jax.devices()[0].memory_stats() or {}
    traced = tracer.result() if tracer is not None else None
    shutil.rmtree(workdir, ignore_errors=True)
    del state
    gc.collect()

    t = time.time()
    verdict = check_trained(
        cell, seed, X, y, first_losses, delta_norms, window_losses, control, log
    )
    phases["reference_s"] = time.time() - t

    rounds = dispatches * tr["n_rounds"]
    tokens_per_round = tr["clients"] * tr["batch"] * tr["seq_len"]
    tokens_per_s = rounds * tokens_per_round / elapsed
    log(f"perfbench: set-up {setup_s:.2f}s = " + ", ".join(f"{k} {v:.2f}" for k, v in phases.items()))
    took = sorted(b - a for a, b in zip(ends, ends[1:]))
    log(f"perfbench: {dispatches} dispatches of {tr['n_rounds']} rounds in {elapsed:.3f}s "
        f"(a dispatch: min {took[0]:.4f} median {took[len(took) // 2]:.4f} max {took[-1]:.4f} s); "
        f"compiles inside the window {compiled_in_window}")
    kind = jax.devices()[0].device_kind
    ctx = {
        "kind": "train", "seconds": elapsed, "rounds": rounds,
        "tokens_per_s": tokens_per_s,
        "flops_per_token": peaks.train_flops_per_token(cfg, tr["seq_len"]),
        "device_kind": kind, "memory_peak_bytes": memory_peak(mem),
        "trace": traced,
    }
    return {
        "e2e": {"setup_s": setup_s, "train_tokens_per_s": tokens_per_s},
        "ctx": ctx, "verdict": verdict, "attempted": dispatches, "failed": 0,
        "compiles_in_window": compiled_in_window,
        "memory_peak_bytes": memory_peak(mem), "trace": traced,
        "phases": phases,
    }
