"""Plan capture, serde round-trip, variants, and portable-dialect execution.

Mirrors the reference's plan lifecycle: trace (01-Create-plan.ipynb cells
16-24) -> host/serialize (plan_manager.py) -> download variant -> execute.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pygrid_tpu import serde
from pygrid_tpu.plans import Plan, func2plan, translate_plan
from pygrid_tpu.plans.translators import run_oplist


def _mlp_params():
    k = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(k)
    return [
        jax.random.normal(k1, (28 * 28, 392)) * 0.01,
        jnp.zeros((392,)),
        jax.random.normal(k2, (392, 10)) * 0.01,
        jnp.zeros((10,)),
    ]


def _forward(X, w1, b1, w2, b2):
    h = jnp.maximum(X @ w1 + b1, 0.0)
    return h @ w2 + b2


def _training_step(X, y, lr, w1, b1, w2, b2):
    """The reference training plan shape: forward+softmax-CE+SGD step
    (01-Create-plan.ipynb cell 16, traced with autograd)."""

    def loss_fn(params):
        logits = _forward(X, *params)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(y * logp, axis=-1))

    params = (w1, b1, w2, b2)
    loss, grads = jax.value_and_grad(loss_fn)(params)
    new_params = tuple(p - lr * g for p, g in zip(params, grads))
    acc = jnp.mean(
        (jnp.argmax(_forward(X, *params), -1) == jnp.argmax(y, -1)).astype(
            jnp.float32
        )
    )
    return (loss, acc) + new_params


@pytest.fixture(scope="module")
def training_plan():
    plan = Plan(name="training_plan", fn=_training_step)
    X = np.zeros((8, 784), np.float32)
    y = np.zeros((8, 10), np.float32)
    return plan.build(X, y, np.float32(0.1), *[np.asarray(p) for p in _mlp_params()])


def test_build_produces_all_variants(training_plan):
    assert training_plan.is_built
    assert translate_plan(training_plan, "list")
    assert isinstance(translate_plan(training_plan, "xla"), bytes)
    assert "lambda" in translate_plan(training_plan, "code")  # jaxpr text
    # syft.js-era aliases accepted (reference routes.py:228-233)
    assert translate_plan(training_plan, "torchscript") == translate_plan(
        training_plan, "xla"
    )


def test_plan_executes_and_learns(training_plan):
    params = _mlp_params()
    X = np.random.RandomState(0).randn(8, 784).astype(np.float32)
    labels = np.random.RandomState(1).randint(0, 10, 8)
    y = np.eye(10, dtype=np.float32)[labels]
    out = training_plan(X, y, np.float32(0.5), *[np.asarray(p) for p in params])
    loss1 = float(out[0])
    out2 = training_plan(X, y, np.float32(0.5), *[np.asarray(p) for p in out[2:]])
    assert float(out2[0]) < loss1  # one SGD step reduced loss


def test_plan_serde_roundtrip_executes_without_live_fn(training_plan):
    blob = serde.serialize(training_plan)
    plan2 = serde.deserialize(blob)
    assert plan2.fn is None and plan2.exported_blob is not None
    params = _mlp_params()
    X = np.random.RandomState(0).randn(8, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[np.arange(8) % 10]
    args = (X, y, np.float32(0.1), *[np.asarray(p) for p in params])
    ref = training_plan(*args)
    out = plan2(*args)
    for a, b in zip(ref, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_state_plan_injection_and_update():
    """State tensors are implicit trailing inputs; updating plan.state between
    rounds changes execution (the model-centric FL flow)."""
    from pygrid_tpu.plans.state import State

    w = np.full((3,), 2.0, np.float32)
    plan = Plan(name="scale", fn=lambda x, w: x * w, state=State.from_tensors([w]))
    plan.build(np.zeros((3,), np.float32))
    x = np.array([1.0, 2.0, 3.0], np.float32)
    np.testing.assert_allclose(plan(x), x * 2.0)
    plan.state = State.from_tensors([np.full((3,), 5.0, np.float32)])
    np.testing.assert_allclose(plan(x), x * 5.0)  # NOT baked-in consts
    # survives the wire: state rides along, still injected
    plan2 = serde.deserialize(serde.serialize(plan))
    np.testing.assert_allclose(plan2(x), x * 5.0)


def test_single_variant_download_is_smaller():
    """Worker downloads carry one variant (translate_plan), not the full
    plan — the reference serves receive_operations_as variants the same way."""
    plan = Plan(name="mm", fn=lambda a, b: a @ b)
    plan.build(np.zeros((64, 64), np.float32), np.zeros((64, 64), np.float32))
    full = len(serde.serialize(plan))
    one_variant = len(serde.serialize(translate_plan(plan, "xla")))
    assert one_variant < full
    # and the variants survive the wire for the hosting path
    plan2 = serde.deserialize(serde.serialize(plan))
    assert plan2.oplist is not None and "lambda" in plan2.code


def test_unbuilt_plan_is_not_built():
    plan = Plan(name="x", fn=lambda a: a)
    assert not plan.is_built
    from pygrid_tpu.plans.state import State

    s = State([])
    assert Plan(name="y", state=s).state is s  # explicit empty State kept


def test_func2plan_decorator():
    @func2plan(args_shape=[(4, 3), (3, 2)])
    def matmul_plan(a, b):
        return a @ b

    a = np.random.randn(4, 3).astype(np.float32)
    b = np.random.randn(3, 2).astype(np.float32)
    np.testing.assert_allclose(matmul_plan(a, b), a @ b, rtol=1e-5)
    assert matmul_plan.name == "matmul_plan"


def test_oplist_dialect_executes_training_plan(training_plan):
    """The portable 'list' dialect must be executable by the reference
    interpreter and agree with the compiled plan."""
    oplist = translate_plan(training_plan, "list")
    # round-trip the dialect over the wire first
    oplist = serde.deserialize(serde.serialize(oplist))
    params = _mlp_params()
    X = np.random.RandomState(2).randn(8, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[np.arange(8) % 10]
    args = (X, y, np.float32(0.1), *[np.asarray(p) for p in params])
    ref = training_plan(*args)
    out = run_oplist(oplist, *args)
    for a, b in zip(ref, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_oplist_numpy_backend_runs_training_plan(training_plan):
    """A client with ONLY numpy — no jax, no XLA — can execute the hosted
    grad-traced training plan from the wire dialect and match the compiled
    output (the tfjs-analog portable variant must be executable,
    reference plan_manager.py:119-149)."""
    oplist = serde.deserialize(serde.serialize(translate_plan(training_plan, "list")))
    params = _mlp_params()
    X = np.random.RandomState(3).randn(8, 784).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[np.arange(8) % 10]
    args = (X, y, np.float32(0.1), *[np.asarray(p) for p in params])
    ref = training_plan(*args)
    out = run_oplist(oplist, *args, backend="numpy")
    for a, b in zip(ref, out):
        assert type(np.asarray(b)) is np.ndarray
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_oplist_numpy_backend_unknown_op_is_typed_error():
    from pygrid_tpu.utils.exceptions import PlanTranslationError

    bogus = {
        "constvars": [], "consts": [], "invars": [0],
        "eqns": [{"op": "no_such_op", "in": [{"var": 0}], "out": [1], "params": {}}],
        "outvars": [{"var": 1}],
    }
    with pytest.raises(PlanTranslationError, match="no_such_op"):
        run_oplist(bogus, np.ones(2), backend="numpy")


def test_oplist_runs_cnn_training_plan_both_backends():
    """The portable dialect covers the CNN training plan — conv
    forward/backward (incl. the lhs-dilated transpose conv the input
    gradient emits), maxpool (reduce_window_max) and its scatter
    gradient (select_and_scatter_add) — on the jax interpreter AND on a
    numpy-only client (the tfjs-analog consumer, reference
    plan_manager.py:119-149)."""
    import jax

    from pygrid_tpu.models import cnn
    from pygrid_tpu.plans.plan import Plan

    params = [np.asarray(p) for p in cnn.init(jax.random.PRNGKey(0))]
    rng = np.random.RandomState(7)
    X = rng.rand(2, 28, 28, 1).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 2)]
    plan = Plan(name="training_plan", fn=cnn.training_step)
    plan.build(X, y, np.float32(0.1), *params)
    ref = cnn.training_step(X, y, np.float32(0.1), *params)
    oplist = serde.deserialize(serde.serialize(plan.oplist))
    for backend in ("jax", "numpy"):
        out = run_oplist(
            oplist, X, y, np.float32(0.1), *params, backend=backend
        )
        for a, b in zip(ref, out):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            )


def test_oplist_runs_transformer_training_plan_both_backends():
    """The portable dialect covers the TRANSFORMER training plan — the
    flagship family: embedding gather + its scatter-add VJP, the loss's
    take_along_axis (batched gather with FILL_OR_DROP), layernorm
    (rsqrt), softmax (reduce_max/exp), gelu — on the jax interpreter AND
    on a numpy-only client. The reference's portable variant never went
    past MLPs (plan_manager.py:119-149); this proves a foreign client
    can train the framework's flagship model from the published dialect."""
    import jax

    from pygrid_tpu.models import transformer
    from pygrid_tpu.plans.plan import Plan

    cfg = transformer.TransformerConfig(
        vocab=64, d_model=32, n_heads=2, d_ff=64, n_layers=2, max_len=16
    )
    step = transformer.make_training_step(cfg)
    params = [np.asarray(p) for p in transformer.init(jax.random.PRNGKey(0), cfg)]
    rng = np.random.RandomState(11)
    X = rng.randint(0, cfg.vocab, (2, 16)).astype(np.int32)
    y = rng.randint(0, cfg.vocab, (2, 16)).astype(np.int32)
    plan = Plan(name="training_plan", fn=step)
    plan.build(X, y, np.float32(0.1), *params)
    ref = step(X, y, np.float32(0.1), *params)
    oplist = serde.deserialize(serde.serialize(plan.oplist))
    for backend in ("jax", "numpy"):
        out = run_oplist(
            oplist, X, y, np.float32(0.1), *params, backend=backend
        )
        for a, b in zip(ref, out):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            )


def test_numpy_gather_scatter_match_lax():
    """Direct parity of the numpy gather/scatter-add executors vs lax on
    shapes beyond what the transformer plan emits: 2-d slices from a 3-d
    operand, CLIP clamping of hostile indices, FILL_OR_DROP dropping
    out-of-bounds updates."""
    import jax.numpy as jnp
    from jax import lax

    from pygrid_tpu.plans.translators import _INTERP_TABLE, _NUMPY_TABLE

    rng = np.random.RandomState(5)
    a = rng.randn(5, 4, 3).astype(np.float32)

    def both(op, *invals, params):
        ref = np.asarray(_INTERP_TABLE[op](*map(jnp.asarray, invals), params))
        got = _NUMPY_TABLE[op](*invals, params)
        assert np.asarray(got).dtype == ref.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float64), np.asarray(ref, np.float64),
            rtol=1e-6, equal_nan=True,
        )

    # rows-of-planes gather, one index out of bounds -> CLIP clamps
    idx = np.array([[0], [4], [9]], np.int32)
    both(
        "gather", a, idx,
        params={
            "dimension_numbers": [[1, 2], [0], [0], [], []],
            "slice_sizes": [1, 4, 3],
            "mode": {"__repr__": "GatherScatterMode.CLIP"},
            "fill_value": None,
        },
    )
    # same gather under FILL_OR_DROP -> the OOB row becomes fill_value
    both(
        "gather", a, idx,
        params={
            "dimension_numbers": [[1, 2], [0], [0], [], []],
            "slice_sizes": [1, 4, 3],
            "mode": {"__repr__": "GatherScatterMode.FILL_OR_DROP"},
            "fill_value": -7.0,
        },
    )
    # fill_value=None must resolve identically on both backends (jax
    # fills NaN for floats / extremes for ints — the numpy reference
    # interpreter is what foreign clients validate against)
    both(
        "gather", a, idx,
        params={
            "dimension_numbers": [[1, 2], [0], [0], [], []],
            "slice_sizes": [1, 4, 3],
            "mode": {"__repr__": "GatherScatterMode.FILL_OR_DROP"},
            "fill_value": None,
        },
    )
    both(
        "gather", a.astype(np.int32), idx,
        params={
            "dimension_numbers": [[1, 2], [0], [0], [], []],
            "slice_sizes": [1, 4, 3],
            "mode": {"__repr__": "GatherScatterMode.FILL_OR_DROP"},
            "fill_value": None,
        },
    )
    # bfloat16 operand (a supported wire dtype): numpy sees kind-'V',
    # jax sees inexact — both backends must still agree, incl. NaN fill
    import ml_dtypes

    for mode in ("CLIP", "FILL_OR_DROP"):
        both(
            "gather", a.astype(ml_dtypes.bfloat16), idx,
            params={
                "dimension_numbers": [[1, 2], [0], [0], [], []],
                "slice_sizes": [1, 4, 3],
                "mode": {"__repr__": f"GatherScatterMode.{mode}"},
                "fill_value": None,
            },
        )
    # scatter-add with an OOB row: FILL_OR_DROP must drop it
    upd = rng.randn(3, 4, 3).astype(np.float32)
    both(
        "scatter-add", a, idx, upd,
        params={
            "dimension_numbers": [[1, 2], [0], [0], [], []],
            "mode": {"__repr__": "GatherScatterMode.FILL_OR_DROP"},
        },
    )


def test_hostile_scatter_params_typed_error():
    """Malformed remote-supplied scatter dimension numbers must fail as
    PlanTranslationError on both backends (WIRE.md §6), never as a raw
    IndexError escaping the interpreter."""
    from pygrid_tpu.utils.exceptions import PlanTranslationError

    a = np.zeros((3, 4), np.float32)
    idx = np.zeros((2, 1), np.int32)
    upd = np.zeros((2, 4), np.float32)
    evil = {
        "constvars": [], "consts": [], "invars": [0, 1, 2],
        "eqns": [{
            "op": "scatter-add",
            "in": [{"var": 0}, {"var": 1}, {"var": 2}],
            "out": [3],
            "params": {
                # scatter dim 7 does not exist on a rank-2 operand
                "dimension_numbers": [[1], [0], [7], [], []],
                "mode": {"__repr__": "GatherScatterMode.CLIP"},
            },
        }],
        "outvars": [{"var": 3}],
    }
    for backend in ("jax", "numpy"):
        with pytest.raises(
            PlanTranslationError, match="invalid params|allocation bound"
        ):
            run_oplist(evil, a, idx, upd, backend=backend)


def test_numpy_windowed_ops_match_lax():
    """Direct parity of the three windowed numpy ops vs lax on shapes the
    plan corpus doesn't hit (odd strides, asymmetric padding, window
    dilation, grouped + dilated conv)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pygrid_tpu.plans.translators import (
        _np_conv,
        _np_reduce_window_max,
        _np_select_and_scatter_add,
    )

    rng = np.random.RandomState(0)
    x = rng.randn(2, 9, 11, 3).astype(np.float32)
    p = {
        "window_dimensions": [1, 3, 2, 1],
        "window_strides": [1, 2, 3, 1],
        "padding": [[0, 0], [1, 2], [0, 1], [0, 0]],
        "base_dilation": [1, 1, 1, 1],
        "window_dilation": [1, 2, 1, 1],
    }
    want = lax.reduce_window(
        x, -jnp.inf, lax.max,
        tuple(p["window_dimensions"]), tuple(p["window_strides"]),
        [tuple(q) for q in p["padding"]],
        window_dilation=tuple(p["window_dilation"]),
    )
    np.testing.assert_allclose(_np_reduce_window_max(x, p), np.asarray(want))

    # select_and_scatter_add vs the VJP of maxpool
    p2 = {
        "select_prim": {"__repr__": "ge"},
        "window_dimensions": [1, 2, 2, 1],
        "window_strides": [1, 2, 2, 1],
        "padding": [[0, 0], [1, 0], [0, 1], [0, 0]],
    }
    src_shape = lax.reduce_window(
        x, -jnp.inf, lax.max,
        tuple(p2["window_dimensions"]), tuple(p2["window_strides"]),
        [tuple(q) for q in p2["padding"]],
    ).shape
    src = rng.randn(*src_shape).astype(np.float32)

    def pool(v):
        return lax.reduce_window(
            v, -jnp.inf, lax.max,
            tuple(p2["window_dimensions"]), tuple(p2["window_strides"]),
            [tuple(q) for q in p2["padding"]],
        )

    _, vjp = jax.vjp(pool, jnp.asarray(x))
    want2 = vjp(jnp.asarray(src))[0]
    np.testing.assert_allclose(
        _np_select_and_scatter_add(src, x, p2), np.asarray(want2)
    )

    # grouped, dilated, strided conv with asymmetric padding
    lhs = rng.randn(2, 10, 12, 4).astype(np.float32)
    ker = rng.randn(3, 3, 2, 6).astype(np.float32)  # HWIO, groups=2
    dn = lax.conv_dimension_numbers(lhs.shape, ker.shape, ("NHWC", "HWIO", "NHWC"))
    kwargs = dict(
        window_strides=(2, 1),
        padding=[(1, 2), (0, 1)],
        lhs_dilation=(1, 2),
        rhs_dilation=(2, 1),
        dimension_numbers=dn,
        feature_group_count=2,
    )
    want3 = lax.conv_general_dilated(lhs, ker, **kwargs)
    p3 = {
        "window_strides": [2, 1],
        "padding": [[1, 2], [0, 1]],
        "lhs_dilation": [1, 2],
        "rhs_dilation": [2, 1],
        "dimension_numbers": [list(dn.lhs_spec), list(dn.rhs_spec), list(dn.out_spec)],
        "feature_group_count": 2,
        "batch_group_count": 1,
    }
    np.testing.assert_allclose(
        _np_conv(lhs, ker, p3), np.asarray(want3), rtol=1e-5, atol=1e-5
    )


def test_numpy_scatter_tie_break_matches_lax():
    """Repeated values (post-ReLU zeros, quantized inputs) force ties in
    every window — the first-max row-major rule must match XLA's 'ge'
    scan order or maxpool gradients silently diverge between backends."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from pygrid_tpu.plans.translators import _np_select_and_scatter_add

    rng = np.random.RandomState(1)
    x = rng.randint(0, 3, (2, 8, 8, 2)).astype(np.float32)  # heavy ties
    p = {
        "select_prim": {"__repr__": "ge"},
        "window_dimensions": [1, 2, 2, 1],
        "window_strides": [1, 2, 2, 1],
        "padding": [[0, 0], [0, 0], [0, 0], [0, 0]],
    }

    def pool(v):
        return lax.reduce_window(
            v, -jnp.inf, lax.max,
            tuple(p["window_dimensions"]), tuple(p["window_strides"]),
            [tuple(q) for q in p["padding"]],
        )

    src = rng.randn(*pool(jnp.asarray(x)).shape).astype(np.float32)
    _, vjp = jax.vjp(pool, jnp.asarray(x))
    want = vjp(jnp.asarray(src))[0]
    np.testing.assert_allclose(
        _np_select_and_scatter_add(src, x, p), np.asarray(want)
    )


def test_windowed_ops_hostile_params_bounded():
    """Huge padding/dilation through the windowed ops must fail typed on
    both backends (allocation bound), never attempt the allocation."""
    from pygrid_tpu.utils.exceptions import PlanTranslationError

    big = 1 << 40
    evil_pool = {
        "constvars": [], "consts": [], "invars": [0],
        "eqns": [{"op": "reduce_window_max", "params": {
            "window_dimensions": [1], "window_strides": [1],
            "padding": [[0, big]], "base_dilation": [1],
            "window_dilation": [1],
        }, "in": [{"var": 0}], "out": [1]}],
        "outvars": [{"var": 1}],
    }
    for backend in ("numpy", "jax"):
        with pytest.raises(PlanTranslationError, match="allocation bound|invalid params"):
            run_oplist(evil_pool, np.ones(4, np.float32), backend=backend)

    # lhs-dilated conv whose intermediate (not output) explodes
    from pygrid_tpu.plans.translators import _np_conv

    lhs = np.ones((1, 4, 1), np.float32)    # NWC-ish 1-spatial-dim conv
    ker = np.ones((1, 1, 1), np.float32)
    p = {
        "window_strides": [1],
        "padding": [[0, -(3 * (1 << 27))]],
        "lhs_dilation": [1 << 27],
        "rhs_dilation": [1],
        "dimension_numbers": [[0, 2, 1], [2, 1, 0], [0, 2, 1]],
        "feature_group_count": 1,
        "batch_group_count": 1,
    }
    with pytest.raises(PlanTranslationError, match="allocation bound"):
        _np_conv(lhs, ker, p)
