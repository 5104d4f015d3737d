"""Plan — a traced, serializable, executable program.

Execution-plane parity: syft ``Plan`` (traced op list + torchscript + tfjs
variants) as consumed by the reference PlanManager
(``syft_assets/plan_manager.py:24-59,119-149``) and built in the model-centric
example (``examples/model-centric/01-Create-plan.ipynb`` cells 16-24,
``plan.build(..., trace_autograd=True)``).

TPU-native redesign: a Plan is captured once with ``jax.make_jaxpr`` and
``jax.export`` (StableHLO), so the stored artifact is what XLA actually
compiles — no interpreter in the hot loop. Three variants mirror the
reference's list/torchscript/tfjs triple:

- ``"list"`` — portable op-list dialect (JSON-able jaxpr walk) for clients
  without an XLA runtime; see :mod:`pygrid_tpu.plans.translators`.
- ``"xla"``  — serialized ``jax.export`` artifact (multi-platform cpu+tpu
  StableHLO); the variant Nodes execute. Torchscript analog.
- ``"code"`` — human-readable jaxpr text (syft ``plan.code`` analog).

``trace_autograd=True`` has no dedicated machinery here: a JAX training step
calls ``jax.grad`` inside the traced function, so the backward pass is simply
part of the captured program.
"""

from __future__ import annotations

import uuid
from typing import Any, Callable, Sequence

import jax
import numpy as np
from jax import export as jax_export

from pygrid_tpu.plans.state import State
from pygrid_tpu.serde import register_serde
from pygrid_tpu.utils.exceptions import PlanInvalidError


def _export_platforms() -> tuple[str, ...]:
    # Export for both so a plan traced on a CPU client runs on a TPU node.
    return ("cpu", "tpu")


def _export(fn: Callable, example_args: Sequence[Any]) -> jax_export.Exported:
    return jax_export.export(jax.jit(fn), platforms=_export_platforms())(
        *example_args
    )


@register_serde(name="pygrid.Plan")
class Plan:
    """A built plan. Call it like a function."""

    def __init__(
        self,
        name: str = "",
        id: str | None = None,
        fn: Callable | None = None,
        state: State | None = None,
        input_specs: list[dict] | None = None,
        exported_blob: bytes | None = None,
        oplist: list | None = None,
        code: str = "",
    ) -> None:
        self.name = name
        self.id = id or uuid.uuid4().hex
        self.fn = fn
        self.state = state if state is not None else State()
        #: how many trailing plan inputs are fed from ``self.state`` (syft
        #: parity: state tensors are implicit inputs appended at call time,
        #: so updating plan.state between FL rounds changes execution)
        self.n_state_inputs = 0
        self.input_specs = input_specs or []
        self.exported_blob = exported_blob
        self.oplist = oplist
        self.code = code
        self._jitted: Callable | None = None
        self._exported: jax_export.Exported | None = None
        # "built" means the wire artifacts exist — a live fn alone is not
        # built until .build() captures jaxpr + exported StableHLO.
        self.is_built = exported_blob is not None

    # --- build -------------------------------------------------------------

    def build(self, *example_args: Any) -> "Plan":
        """Trace ``fn`` on example args, capture jaxpr + exported StableHLO.

        If the plan carries a State, its tensors are appended as trailing
        inputs — callers then invoke the plan with data args only and the
        current ``self.state`` is injected at call time.
        """
        if self.fn is None:
            raise PlanInvalidError("Plan has no function to build")
        from pygrid_tpu.plans.translators import jaxpr_to_oplist

        state_tensors = [np.asarray(t) for t in self.state.tensors()]
        self.n_state_inputs = len(state_tensors)
        example_args = tuple(example_args) + tuple(state_tensors)
        closed = jax.make_jaxpr(self.fn)(*example_args)
        self.code = str(closed)
        self.oplist = jaxpr_to_oplist(closed)
        exported = _export(self.fn, example_args)
        self._exported = exported
        self.exported_blob = bytes(exported.serialize())
        self.input_specs = [
            {"shape": list(np.shape(a)), "dtype": str(np.asarray(a).dtype)}
            for a in example_args
        ]
        self.is_built = True
        return self

    # --- execute -----------------------------------------------------------

    def _callable(self) -> Callable:
        if self.fn is not None:
            if self._jitted is None:
                self._jitted = jax.jit(self.fn)
            return self._jitted
        if self._exported is None:
            if self.exported_blob is None:
                raise PlanInvalidError("Plan is not built")
            self._exported = jax_export.deserialize(bytearray(self.exported_blob))
        return self._exported.call

    def __call__(self, *args: Any):
        if self.n_state_inputs:
            args = tuple(args) + tuple(self.state.tensors())
        return self._callable()(*args)


    # --- serde -------------------------------------------------------------

    def _bufferize(self) -> dict:
        # The full plan (all variants) crosses the wire only on host upload —
        # the reference pays the same (server stores list/ts/tfjs variants,
        # plan_manager.py:24-59). Worker downloads go through
        # translate_plan(plan, variant) and carry exactly one variant
        # (routes serve receive_operations_as — reference routes.py:228-233).
        return {
            "name": self.name,
            "id": self.id,
            "state": self.state,
            "n_state_inputs": self.n_state_inputs,
            "input_specs": self.input_specs,
            "exported_blob": self.exported_blob,
            "oplist": self.oplist,
            "code": self.code,
        }

    @classmethod
    def _unbufferize(cls, data: dict) -> "Plan":
        plan = cls(
            name=data["name"],
            id=data["id"],
            state=data["state"],
            input_specs=data["input_specs"],
            exported_blob=data["exported_blob"],
            oplist=data["oplist"],
            code=data["code"],
        )
        # .get: blobs from builds predating state injection never injected
        # state, so 0 reproduces their behavior exactly
        plan.n_state_inputs = data.get("n_state_inputs", 0)
        return plan

    def __repr__(self) -> str:
        return (
            f"Plan(name={self.name!r}, id={self.id!r}, built={self.is_built}, "
            f"inputs={self.input_specs})"
        )


def func2plan(
    args_shape: Sequence[Sequence[int]],
    state: Sequence[Any] | None = None,
    args_dtypes: Sequence[Any] | None = None,
    name: str | None = None,
):
    """Decorator: trace a python function into a built :class:`Plan`.

    Parity with syft's ``@sy.func2plan(args_shape=..., state=...)`` used in
    the reference notebooks (01-Create-plan.ipynb cell 16). ``args_shape``
    gives example input shapes (zeros are used as tracing exemplars);
    ``state`` optionally attaches model parameters carried with the plan.
    """

    def decorator(fn: Callable) -> Plan:
        dtypes = list(args_dtypes or [np.float32] * len(args_shape))
        example_args = [
            np.zeros(tuple(s), dtype=d) for s, d in zip(args_shape, dtypes)
        ]
        plan = Plan(name=name or fn.__name__, fn=fn)
        if state is not None:
            plan.state = State.from_tensors(list(state))
        plan.build(*example_args)
        return plan

    return decorator
